"""Compile-only checks for the TPU: libtpu is installed here, and a described
(not attached) v5e topology takes ``jit(...).lower(...).compile()``, which
raises what Mosaic would raise on the chip — a tile over VMEM, a slice off
the tiling. Nothing runs, so this says nothing about results or times.

All such tests live in THIS file: one process loads the TPU's library, and
under pytest-xdist a file goes to one worker. The topology is described
inside a fixture, never at import.
"""

import re

import pytest


@pytest.fixture(scope="module")
def one_chip():
    import os

    import jax
    from jax.sharding import SingleDeviceSharding

    env = pytest.MonkeyPatch()
    for name, value in {
        "TPU_LOG_DIR": "disabled",
        "TPU_ACCELERATOR_TYPE": "v5litepod-4",
        "TPU_WORKER_HOSTNAMES": "localhost",
        "TPU_SKIP_MDS_QUERY": "1",
    }.items():
        if name not in os.environ:
            env.setenv(name, value)
    # a program compiled for a described device is written to the persistent
    # cache and cannot be read back without the chip: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        from jax.experimental import topologies

        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        env.undo()


@pytest.mark.parametrize(
    "Hq,Hkv,D,Dv",
    [(32, 8, 128, 128), (128, 128, 192, 128)],
    ids=["gqa-32-8-128", "mla-128-192-128"],
)
@pytest.mark.parametrize("q_offset", [0, 2048])
def test_flash_chunk_compiles_for_v5e(one_chip, Hq, Hkv, D, Dv, q_offset):
    """The docqa cells' chunk calls (2048 query rows at offset 0 and 2048,
    bf16) with the tiles the kernel chooses: Mosaic takes them inside the
    VMEM limit the call asks for."""
    import jax
    import jax.numpy as jnp

    from modal_examples_tpu.ops.flash_attention import _flash_forward

    C = 2048
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
    fn = lambda q, k, v: _flash_forward(
        q, k, v, causal=True, sm_scale=D**-0.5, interpret=False,
        q_offset=q_offset,
    )
    compiled = jax.jit(fn).lower(
        shape(1, Hq, C, D), shape(1, Hkv, q_offset + C, D),
        shape(1, Hkv, q_offset + C, Dv),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


# -- the docqa cells' tail chunk programs, by width (PR 33) ---------------------------------

#: width of the last chunk -> the tiles its flash call takes over 2048 cached
#: keys and its own. ``choose_blocks`` takes divisors, and 2048 + 128 has no
#: key block longer than 128: ``flash_attention_chunked`` pads the keys to 3072
TAIL_BLOCKS = {128: (128, 1024), 256: (256, 1024), 512: (512, 1024), 1024: (1024, 1024),
               2048: (1024, 1024)}


@pytest.mark.parametrize("D,Dv", [(128, 128), (192, 128)], ids=["gqa-128", "mla-192-128"])
def test_the_flash_call_of_a_tail_chunk_takes_these_tiles(D, Dv):
    from modal_examples_tpu.ops.flash_attention import choose_blocks, padded_kv_len

    assert [padded_kv_len(2048 + w) for w in TAIL_BLOCKS] == [3072, 3072, 3072, 3072, 4096]
    assert [padded_kv_len(n) for n in (80, 1024, 1025, 2048)] == [80, 1024, 2048, 2048]
    assert {
        w: choose_blocks(w, padded_kv_len(2048 + w), D, Dv, 2) for w in TAIL_BLOCKS
    } == TAIL_BLOCKS
    # unpadded, the key block is no longer than the tail: what the padding is for
    assert choose_blocks(128, 2048 + 128, D, Dv, 2) == (128, 128)


def _cell_operands(one_chip, family):
    """A configuration of the benchmark (its file, its pages) as shapes on
    the described chip: ``(module, cfg, params, k_pages, v_pages, S)``."""
    import jax
    import jax.numpy as jnp

    from modal_examples_tpu.models import deepseek_v2, llama
    from modal_examples_tpu.models.quantize import quantize_llama

    module, config_class, name, n_pages = {  # the cell's file, its n_pages
        "mistral": (llama, llama.LlamaConfig, "mistral-7b-int8", 3072),
        "mixtral": (llama, llama.LlamaConfig, "mixtral-8x7b-int8-1chip", 4096),
        "deepseek": (deepseek_v2, deepseek_v2.DeepseekV2Config, "deepseek-v2-int8-ep4", 12288),
    }[family]
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    cfg = config_class.from_hf_config(f"benchmarks/serving/configs/{name}.json")
    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(
            lambda k: quantize_llama(module.init_params(k, cfg), cfg.quant_targets),
            jax.random.PRNGKey(0),
        ),
    )
    layers = getattr(cfg, "n_cache_layers", cfg.n_layers)
    k_pages, v_pages = (
        S((layers, n_pages, 16, *leaf), jnp.bfloat16) for leaf in cfg.cache_leaf_shapes
    )
    return module, cfg, params, k_pages, v_pages, S


@pytest.fixture(scope="module")
def chunk_program(one_chip):
    """``compile(family, width)``: the engine's chunk program at offset 2048
    for a docqa configuration of the benchmark (its file, its pages), as
    shapes on the described chip."""
    import jax
    import jax.numpy as jnp

    from modal_examples_tpu.serving.engine import LLMEngine

    eng = object.__new__(LLMEngine)  # the program's body, without an engine's arrays
    eng._attn_impl, eng.mesh, eng._chunk_jits = "flash", None, {}

    def compile(family, width):
        _, cfg, params, k_pages, v_pages, S = _cell_operands(one_chip, family)
        i32 = lambda *s: S(s, jnp.int32)  # noqa: E731
        f32 = lambda *s: S(s, jnp.float32)  # noqa: E731
        sampler = (S((2,), jnp.uint32), f32(1), f32(1), i32(1), i32(1), i32(1))
        return eng._chunk_jit(2048).lower(
            params, i32(1, width), k_pages, v_pages, i32(1, 256), i32(1), *sampler, cfg=cfg
        ).compile()

    # the kernels pick interpret= from the backend at trace time
    backend, jax.default_backend = jax.default_backend, lambda: "tpu"
    try:
        yield compile
    finally:
        jax.default_backend = backend


def test_mistrals_five_tail_programs_compile_for_a_v5e(chunk_program):
    """Every width the last chunk of a docqa prompt can take, at offset 2048:
    each goes through Mosaic, is named for its offset, and a narrower one
    needs less beside weights and pages than the 2048-wide call (0.60 GiB),
    which every chunked prompt took before."""
    temps = {}
    for width in TAIL_BLOCKS:
        compiled = chunk_program("mistral", width)
        text = compiled.as_text()
        assert "tpu_custom_call" in text and "jit_prefill_chunk_off2048" in text
        temps[width] = compiled.memory_analysis().temp_size_in_bytes
    assert sorted(temps.values()) == [temps[w] for w in sorted(temps)]
    assert temps[1024] < temps[2048] < 0.7 * GIB


def test_deepseeks_tail_program_compiles_at_widths_192_and_128_for_a_v5e(chunk_program):
    """The 1 x 512 tail over 2048 cached latents expanded again (q/k 192 wide,
    values 128): under the 2048-wide call's 1.31 GiB of temporaries."""
    compiled = chunk_program("deepseek", 512)
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0 * GIB  # 0.81


# -- the decode block's attention at the serving cells' own shapes (PR 35) -------------------


@pytest.fixture(scope="module")
def decode_block(one_chip):
    """``lowered(family)``: the engine's decode block of 8 steps for a
    configuration of the benchmark (its file, its slots and pages), with
    ``paged_impl`` left unset, as shapes on the described chip."""
    import jax
    import jax.numpy as jnp

    from modal_examples_tpu.serving.engine import LLMEngine

    def lowered(family):
        module, cfg, params, k_pages, v_pages, S = _cell_operands(one_chip, family)
        i32 = lambda *s: S(s, jnp.int32)  # noqa: E731
        f32 = lambda *s: S(s, jnp.float32)  # noqa: E731
        eng = object.__new__(LLMEngine)  # the program's body, without an engine's arrays
        eng._model, eng.cfg, eng.mesh = module, cfg, None
        eng.paged_impl, eng.scatter_impl = None, "xla"
        eng._block_counts, eng.decode_block = ("routed_pairs",) * bool(cfg.counts_routed_pairs), 8
        B = 16
        return jax.jit(eng._decode_block_fn, donate_argnums=(1, 2)).lower(
            params, k_pages, v_pages, i32(B), i32(B), S((B,), bool), i32(B),
            i32(B, 256), S((B,), bool), S((2,), jnp.uint32), f32(B), f32(B), i32(B), i32(B),
        )

    # the plan and the kernels read the backend at trace time
    backend, jax.default_backend = jax.default_backend, lambda: "tpu"
    try:
        yield lowered
    finally:
        jax.default_backend = backend


@pytest.mark.parametrize("family, n_pages", [("mistral", 3072), ("mixtral", 4096)])
def test_decode_block_reads_the_pages_through_the_ragged_kernel_on_a_v5e(
    decode_block, family, n_pages
):
    """With nothing set, the plan picks the ragged kernel for 8 KV heads of
    128 on the chip: the block goes through Mosaic inside the default VMEM
    limit (a 2 MiB ring), the cache is handed to the kernel as it lies (the
    flat form's ``(ps * Hkv, D)`` view is a bitcast: no copy of a leaf), and
    the loop's gathered chunk ``[256 positions, 16 slots, 8, 128]`` is gone
    with its two fusions (PERF.md section 6, PR 35)."""
    compiled = decode_block(family).compile()
    text = compiled.as_text()
    layers = {"mistral": 32, "mixtral": 7}[family]
    leaf = f"bf16[{layers},{n_pages},16,8,128]"
    assert "tpu_custom_call" in text and "mtpu.attention" in text
    assert "bf16[256,16,8,128]" not in text  # kv_gather's chunk
    assert leaf in text and not _relaid_out(text, leaf)
    assert not _relaid_out(text, f"bf16[{layers},{n_pages},128,128]")
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * layers * n_pages * 16 * 8 * 128 * 2  # pages in place


def test_a_latent_cache_still_decodes_through_the_loop(decode_block):
    """DeepSeek-V2's plan for an unset ``paged_impl`` is what it was for
    ``xla``: the chunked loop over the latent pages, no Pallas call for the
    attention in the decode block (a 576-wide head is not the ragged
    kernel's). The one kernel in it is the routed layers' grouped matmul
    (PR 40), under ``mtpu.expert_scan``."""
    import re

    text = decode_block("deepseek").as_text(debug_info=True)
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    kernels = [locs[ref] for ref in re.findall(r"@tpu_custom_call\(.*loc\((#loc\d+)\)$", text, re.M)]
    assert len(kernels) == 1 and kernels[0].endswith("mtpu.expert_scan/pallas_call"), kernels
    assert "stablehlo.while" in text


# -- Granite-4.0-H-Micro at its published widths (PR 31) ------------------------------------


@pytest.fixture(scope="module")
def granite(one_chip):
    """The engine's decode block and bucketed prefill for the benchmark's
    configuration (64 slots, 6144 pages of 16, 2048 positions), as shapes on
    the described chip: nothing is allocated."""
    import jax
    import jax.numpy as jnp

    from modal_examples_tpu.models import granite_hybrid as G
    from modal_examples_tpu.serving.engine import LLMEngine

    cfg = G.GraniteHybridConfig(vocab_size=25088)
    slots, n_pages, page_size, pages_per_slot = 64, 6144, 16, 128
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(lambda k: G.init_params(k, cfg), jax.random.PRNGKey(0)),
    )
    pages = S((cfg.n_cache_layers, n_pages, page_size, *cfg.cache_leaf_shapes[0]), jnp.bfloat16)
    state = tuple(S((n, slots, *shape), jnp.dtype(dt)) for n, shape, dt in cfg.state_leaves)
    eng = object.__new__(LLMEngine)  # the two program bodies, without an engine's arrays
    eng._model, eng.cfg, eng.mesh, eng._attn_impl = G, cfg, None, "flash"
    eng.paged_impl, eng.scatter_impl = None, "xla"  # unset: this family's plan is the loop
    eng._block_counts, eng.decode_block = (), 8
    i32 = lambda *s: S(s, jnp.int32)  # noqa: E731
    f32 = lambda *s: S(s, jnp.float32)  # noqa: E731
    key = S((2,), jnp.uint32)

    def block():
        B = slots
        return jax.jit(
            eng._decode_block_fn, donate_argnums=(1, 2), donate_argnames=("state",)
        ).lower(
            params, pages, pages, i32(B), i32(B), S((B,), bool), i32(B),
            i32(B, pages_per_slot), S((B,), bool), key, f32(B), f32(B), i32(B), i32(B),
            state=state,
        ).compile()

    def prefill(bucket):
        B = 4
        return jax.jit(
            eng._prefill_and_sample, donate_argnums=(1, 2), donate_argnames=("state",)
        ).lower(
            params, pages, pages, i32(B, bucket), i32(B, pages_per_slot), i32(B),
            key, f32(B), f32(B), i32(B), i32(B), state=state, slot_ids=i32(B),
        ).compile()

    # the kernels pick interpret= from the backend at trace time
    backend, jax.default_backend = jax.default_backend, lambda: "tpu"
    try:
        yield {"cfg": cfg, "block": block, "prefill": prefill,
               "state_bytes": sum(_nbytes(s) for s in state), "page_bytes": 2 * _nbytes(pages)}
    finally:
        jax.default_backend = backend


def _nbytes(shape):
    import numpy as np

    return int(np.prod(shape.shape)) * shape.dtype.itemsize


GIB = 2**30


def test_granite_decode_block_updates_its_state_in_place_on_a_v5e(granite):
    """The 64-slot decode block with 4.56 GiB of per-slot state: the state
    leaves and the pages are aliased in and out (donated, updated in place)
    and no temporary is a copy of the state leaf or of a paged leaf. The
    paged leaves keep two K/V heads of 64 to a 128-wide row
    (``cfg.kv_fold``): as ``(8, 64)`` the device laid them out pages-minor
    and relaid each out on the way in and out of the block, 1.6 GiB of
    temporaries. Weights 5.6 + state 4.6 + pages 0.75 + this fit the chip's
    15.75 GiB.

    Since PR 37 the state step is the one-pass kernel (the plan's
    ``state_step`` on a TPU at these shapes): a Mosaic call a Mamba segment
    under ``mtpu.ssm_step``, handed the whole leaf and aliased to it, and no
    XLA operation of a layer's ``[64, 64, 64, 128]`` state is left (the
    update fusion and the reduction that read ``h'`` again are gone).
    Attention stays the loop: a 64-wide head is not the ragged kernel's."""
    import re

    compiled = granite["block"]()
    mem = compiled.memory_analysis()
    held = granite["state_bytes"] + granite["page_bytes"]
    assert mem.alias_size_in_bytes >= held  # updated in place, not copied out
    assert mem.temp_size_in_bytes < 1 * GIB  # ISSUE 31's bound
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) < 13.5 * GIB
    text = compiled.as_text()
    assert granite["cfg"].cache_leaf_shapes == ((4, 128), (4, 128))
    assert "bf16[4,6144,16,4,128]" in text and " copy(bf16[4,6144,16,4,128]" not in text
    assert "f32[36,64,64,64,128]" in text  # the state leaf, handed whole to the kernel
    assert not re.search(r"f32\[36,64,64,64,128\]\S* copy\(", text)
    assert "f32[64,64,64,128]" not in text  # no XLA pass over a layer's state
    assert "mtpu.ssm_step" in text and "mtpu.ssm_proj" in text and "mtpu.attention" in text
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    segments = [s for s in granite["cfg"].segments if s[0] == "mamba"]
    assert len(calls) == len(segments) == 5
    assert all("mtpu.ssm_step/" in c and "mtpu.attention" not in c for c in calls)
    assert all("f32[36,64,64,64,128]" in c.split(" custom-call(")[0] for c in calls)  # its output: the leaf


@pytest.mark.parametrize("groups", [1, 8])
def test_the_state_steps_tile_fits_the_scoped_vmem_of_a_v5e(one_chip, groups):
    """The kernel alone at the published widths and the cell's 64 slots,
    with the tile it chooses: the tile in and out, each double-buffered, and
    the 8 MiB the call leaves for the rest are the 16 MiB a v5e's kernel
    gets by default, no more, and Mosaic takes the kernel inside them (it
    refuses the compile otherwise). ``groups`` 8: ``B`` and ``C`` rows
    picked by head."""
    import jax
    import jax.numpy as jnp

    from modal_examples_tpu.ops.ssm_step import TILE_BYTES, ssm_step, ssm_step_tile

    L, S, H, P, N = 36, 64, 64, 64, 128
    ts, th = ssm_step_tile(S, H, P, N)
    assert 4 * ts * th * P * N * 4 + 8 * 2**20 <= 4 * TILE_BYTES + 8 * 2**20 <= 16 * 2**20
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)  # noqa: E731
    compiled = jax.jit(
        lambda ssm, i, *rest: ssm_step(ssm, i, *rest, interpret=False), donate_argnums=0
    ).lower(
        f32(L, S, H, P, N), jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        f32(S, H), f32(S, H, P), f32(S, groups, N), f32(S, groups, N),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == L * S * H * P * N * 4 and mem.temp_size_in_bytes < 2**20


def test_granite_prefill_call_compiles_at_head_width_64_on_a_v5e(granite):
    """The 4 x 256 bucket call: the flash kernel at head width 64 with the
    tile it chooses (one 256 x 256 tile), the chunked scan at chunk 256, one
    scatter of 4 rows into the state leaf in place."""
    from modal_examples_tpu.ops.flash_attention import choose_blocks

    assert choose_blocks(256, 256, 64, 64, 2) == (256, 256)
    compiled = granite["prefill"](256)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= granite["state_bytes"] + granite["page_bytes"]
    assert mem.temp_size_in_bytes < 1 * GIB  # 0.48 GiB; 2.1 before the pages kept two heads to a row
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) < 13.5 * GIB
    text = compiled.as_text()
    assert "tpu_custom_call" in text  # the flash kernel went through Mosaic
    assert "mtpu.ssm_scan" in text and "mtpu.ssm_proj" in text


# -- GLM-5.2's share at its published widths (PR 34) ----------------------------------------


@pytest.fixture(scope="module")
def glm(one_chip):
    """The engine's decode block and chunk program for the benchmark's
    configuration (16 slots, 24576 pages of 16, 18432 positions: three paged
    leaves), as shapes on the described chip: nothing is allocated."""
    import jax
    import jax.numpy as jnp

    from modal_examples_tpu.models import glm_dsa as G
    from modal_examples_tpu.models.quantize import quantize_llama
    from modal_examples_tpu.serving.engine import LLMEngine

    cfg = G.GlmDsaConfig.from_hf_config("benchmarks/serving/configs/glm-5.2-int8-ep16.json")
    slots, n_pages, page_size, pages_per_slot = 16, 24576, 16, 18432 // 16
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(
            lambda k: quantize_llama(G.init_params(k, cfg), cfg.quant_targets),
            jax.random.PRNGKey(0),
        ),
    )
    leaves = [
        S((layers, n_pages, page_size, *leaf), jnp.bfloat16)
        for layers, leaf in zip(cfg.cache_leaf_layers, cfg.cache_leaf_shapes)
    ]
    eng = object.__new__(LLMEngine)  # the program bodies, without an engine's arrays
    eng._model, eng.cfg, eng.mesh, eng._attn_impl = G, cfg, None, "flash"
    eng.paged_impl = eng.scatter_impl = "xla"
    eng._block_counts, eng.decode_block = ("routed_pairs",), 8
    eng._runtime_offset, eng._chunk_jits = True, {}
    i32 = lambda *s: S(s, jnp.int32)  # noqa: E731
    f32 = lambda *s: S(s, jnp.float32)  # noqa: E731
    key = S((2,), jnp.uint32)

    def block():
        B = slots
        return jax.jit(
            eng._decode_block_fn, donate_argnums=(1, 2), donate_argnames=("state",)
        ).lower(
            params, leaves[0], leaves[1], i32(B), i32(B), S((B,), bool), i32(B),
            i32(B, pages_per_slot), S((B,), bool), key, f32(B), f32(B), i32(B), i32(B),
            state=(leaves[2],),
        ).compile()

    def chunk(prefix, width):
        return eng._chunk_jit(prefix).lower(
            params, i32(1, width), leaves[0], leaves[1], i32(1, pages_per_slot), i32(1),
            key, f32(1), f32(1), i32(1), i32(1), i32(1),
            state=(leaves[2],), slot_ids=i32(1), q_offset=i32(), cfg=cfg,
        ).compile()

    # the kernels pick interpret= from the backend at trace time
    backend, jax.default_backend = jax.default_backend, lambda: "tpu"
    try:
        yield {"block": block, "chunk": chunk, "page_bytes": sum(_nbytes(a) for a in leaves),
               "weight_bytes": sum(_nbytes(a) for a in jax.tree.leaves(params))}
    finally:
        jax.default_backend = backend


def _relaid_out(text, shape):
    """Whether the compiled text holds a ``copy`` that produces ``shape``."""
    import re

    return re.search(re.escape(shape) + r"\{[^}]*\} copy\(", text) is not None


def _kernel_scopes(hlo_text):
    """The ``op_name`` of every Mosaic call in a compiled program's text."""
    import re

    return re.findall(
        r'custom_call_target="tpu_custom_call".*?metadata=\{op_name="([^"]*)"', hlo_text
    )


def test_glm_decode_block_gathers_the_selection_in_place_on_a_v5e(glm):
    """The 16-slot decode block over 3.56 GiB of pages in three leaves beside
    6.0 GiB of weights: every leaf aliased in and out, the latent leaf and the
    indexer's leaf indexed in place (no copy of either), no index score
    tensor over heads whole. What is left (PERF.md section 7, PR 34): the
    64-wide rotated-key leaf is laid out anew, whole, every step (a gather of
    64-wide rows: 0.38 GiB of the 0.93 GiB of temporaries)."""
    compiled = glm["block"]()
    mem = compiled.memory_analysis()
    assert 5.9 * GIB < glm["weight_bytes"] < 6.1 * GIB and glm["page_bytes"] == 3.5625 * GIB
    assert mem.alias_size_in_bytes >= glm["page_bytes"]  # updated in place, not copied out
    assert mem.temp_size_in_bytes < 1.0 * GIB  # under ISSUE 34's 2.5
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) < 11 * GIB
    text = compiled.as_text()
    assert not _relaid_out(text, "bf16[8,24576,16,1,512]")
    assert not _relaid_out(text, "bf16[2,24576,16,1,128]")
    assert _relaid_out(text, "bf16[8,24576,16,1,64]")  # the open item: drop this line with it
    assert "[32,16,18432]" not in text and "[16,32,18432]" not in text  # scores over heads, whole
    for scope in ("mtpu.indexer", "mtpu.topk_select", "mtpu.attention", "mtpu.page_gather"):
        assert scope in text
    # the routed layers' tiles go through the grouped-matmul kernel (PR 40): a chip's share,
    # 16 of 256 experts held at an offset, 6144 x 2048 int8 in blocks of 512 columns
    kernels = _kernel_scopes(text)
    assert kernels and all(name.endswith("mtpu.expert_scan/pallas_call") for name in kernels)


def test_glm_chunk_call_over_a_16k_prefix_compiles_for_a_v5e(glm):
    """A 2048-row chunk over the 16384-position prefix bucket, the offset an
    argument: the selected-attention kernel goes through Mosaic at 1024 x 1024
    tiles of width 256, the latents expand a block of 1024 positions at a
    time (whole, the float32 product is 2 GiB), the index scores a block of
    1024 keys at a time (never ``[32, 2048, S]``). 4.0 GiB of temporaries
    beside 9.56 of weights and pages fit the chip's 15.75; ISSUE 34 asked for
    2.5: 1.1 of the rest are three relayouts of the 64-wide leaf, 1.3 the
    routed layer's float32 rows of all 16384 pairs, 0.9 the head dequantised
    for one row (PERF.md section 7, PR 34)."""
    compiled = glm["chunk"](16384, 2048)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= glm["page_bytes"]
    assert mem.temp_size_in_bytes < 4.2 * GIB
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) < 14 * GIB
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "jit_prefill_chunk_pre16384" in text
    assert not _relaid_out(text, "bf16[8,24576,16,1,512]")
    assert not _relaid_out(text, "bf16[2,24576,16,1,128]")
    assert "[32,2048,18432]" not in text and "[2048,32,18432]" not in text
    assert "f32[18432,64,448]" not in text and "f32[1,18432,64,448]" not in text  # expanded whole
    assert "bf16[18,1,64,1024,256]" in text  # keys and values in blocks of 1024 positions
    for scope in ("mtpu.indexer", "mtpu.topk_select", "mtpu.latent_expand", "mtpu.attention"):
        assert scope in text


# -- the routed experts' grouped matmul alone, at the cells' decode shapes (PR 40) ----------


@pytest.mark.parametrize(
    "tokens,top_k,layers,experts,D,F,dtype,block",
    [
        (64, 4, 16, 64, 2048, 1536, "int8", 1536),
        (16, 2, 7, 8, 4096, 14336, "int8", 512),
        (16, 8, 7, 16, 6144, 2048, "bfloat16", 256),
    ],
    ids=["lfm2", "mixtral", "glm-unquantised"],
)
def test_the_expert_kernel_compiles_at_a_decode_steps_shapes_on_a_v5e(
    one_chip, monkeypatch, tokens, top_k, layers, experts, D, F, dtype, block
):
    """``moe_swiglu_sparse`` through the kernel on the whole stacks, with the
    tile and the F block the shapes choose: an LFM2 expert's 9.4 MB of int8
    in one block (19 MB double-buffered: the call raises the scoped VMEM
    limit for it), a Mixtral expert's 176 MB in 28 blocks of 512 columns,
    GLM-5.2's experts left in bf16 (no scales) in 8 blocks of 256. Mosaic
    takes each, no layer's slice of a stack is a temporary, and what the
    layer keeps beside the stacks is the tiles' rows."""
    import jax
    import jax.numpy as jnp

    from modal_examples_tpu.models import moe
    from modal_examples_tpu.models.quantize import QuantizedWeight
    from modal_examples_tpu.ops.expert_swiglu import expert_swiglu_block

    assert moe.expert_tile(tokens, top_k, experts) == 16
    assert expert_swiglu_block(D, F, dtype) == block
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the kernel picks interpret= from it
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731

    def stack(din, dout):
        if dtype != "int8":
            return S((layers, experts, din, dout), jnp.dtype(dtype))
        return QuantizedWeight(
            q=S((layers, experts, din, dout), jnp.int8), scale=S((layers, experts, 1, dout), jnp.float32)
        )

    compiled = jax.jit(
        lambda *a: moe.moe_swiglu_sparse(*a[:-1], layer=a[-1], scan="pallas")
    ).lower(
        stack(D, F), stack(D, F), stack(F, D), S((tokens, D), jnp.bfloat16),
        S((tokens, top_k), jnp.int32), S((tokens, top_k), jnp.float32), S((), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert _kernel_scopes(text) == ["jit(<lambda>)/mtpu.expert_scan/pallas_call"]
    assert not re.search(rf"(s8|bf16)\[(1,)?{experts},{D},{F}\]", text)
    rows = tokens * top_k + experts * 15
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * rows * D * (2 + 4) + 2**20


# -- LFM2-24B-A2B's first 18 layers at their published widths (PR 39) -----------------------


@pytest.fixture(scope="module")
def lfm2(one_chip):
    """The engine's decode block and bucketed prefill for the benchmark's
    configuration (64 slots, 6144 pages of 16, 2048 positions, every expert
    of 16 routed layers in int8), as shapes on the described chip: nothing is
    allocated."""
    import jax
    import jax.numpy as jnp

    from modal_examples_tpu.models import lfm2 as L
    from modal_examples_tpu.models.quantize import quantize_llama
    from modal_examples_tpu.serving.engine import LLMEngine

    cfg = L.Lfm2Config.from_hf_config("benchmarks/serving/configs/lfm2-24b-a2b-int8-1chip.json")
    slots, n_pages, page_size, pages_per_slot = 64, 6144, 16, 128
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(
            lambda k: quantize_llama(L.init_params(k, cfg), cfg.quant_targets),
            jax.random.PRNGKey(0),
        ),
    )
    pages = S((cfg.n_cache_layers, n_pages, page_size, *cfg.cache_leaf_shapes[0]), jnp.bfloat16)
    state = tuple(S((n, slots, *shape), jnp.dtype(dt)) for n, shape, dt in cfg.state_leaves)
    eng = object.__new__(LLMEngine)  # the two program bodies, without an engine's arrays
    eng._model, eng.cfg, eng.mesh, eng._attn_impl = L, cfg, None, "flash"
    eng.paged_impl, eng.scatter_impl = None, "xla"  # unset: this family's plan is the loop
    eng._block_counts, eng.decode_block = ("expert_tile_rows",), 8
    i32 = lambda *s: S(s, jnp.int32)  # noqa: E731
    f32 = lambda *s: S(s, jnp.float32)  # noqa: E731
    key = S((2,), jnp.uint32)

    def block():
        B = slots
        return jax.jit(
            eng._decode_block_fn, donate_argnums=(1, 2), donate_argnames=("state",)
        ).lower(
            params, pages, pages, i32(B), i32(B), S((B,), bool), i32(B),
            i32(B, pages_per_slot), S((B,), bool), key, f32(B), f32(B), i32(B), i32(B),
            state=state,
        ).compile()

    def prefill(bucket):
        B = 4
        return jax.jit(
            eng._prefill_and_sample, donate_argnums=(1, 2), donate_argnames=("state",)
        ).lower(
            params, pages, pages, i32(B, bucket), i32(B, pages_per_slot), i32(B),
            key, f32(B), f32(B), i32(B), i32(B), state=state, slot_ids=i32(B),
        ).compile()

    # the kernels pick interpret= from the backend at trace time
    backend, jax.default_backend = jax.default_backend, lambda: "tpu"
    try:
        yield {"cfg": cfg, "block": block, "prefill": prefill,
               "weight_bytes": sum(_nbytes(a) for a in jax.tree.leaves(params)),
               "state_bytes": sum(_nbytes(s) for s in state), "page_bytes": 2 * _nbytes(pages)}
    finally:
        jax.default_backend = backend


def test_lfm2_decode_block_reads_an_expert_where_it_multiplies_on_a_v5e(lfm2):
    """The 64-slot decode block beside 9.5 GiB of int8 weights, every expert
    of 16 routed layers among them: pages and windows aliased in and out, no
    temporary a copy of a layer's experts (``[64, 2048, 1536]`` int8 is 201
    MB a matrix: the grouped-matmul kernel's index maps pick ``[layer,
    expert]`` out of the whole stack) nor of a paged leaf, two K/V heads of 64
    to a 128-wide page row. The routed layers' tiles are Mosaic calls under
    ``mtpu.expert_scan`` (PR 40: 8 call sites, 4 attention layers and 4
    scanned runs of 3 convolution layers), 76 tiles of 16 rows each, and the
    loop's float32 row buffer ``[4289, 2048]`` is gone. Weights 9.5 + pages
    0.75 + this fit the chip's 15.75 GiB."""
    import re

    compiled = lfm2["block"]()
    mem = compiled.memory_analysis()
    assert 9.4 * GIB < lfm2["weight_bytes"] < 9.6 * GIB  # 10.2 GB
    assert lfm2["page_bytes"] == 0.75 * GIB and lfm2["state_bytes"] == 14 * 64 * 8192
    assert mem.alias_size_in_bytes >= lfm2["page_bytes"] + lfm2["state_bytes"]
    assert mem.temp_size_in_bytes < 0.5 * GIB
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) < 11.5 * GIB
    text = compiled.as_text()
    assert lfm2["cfg"].cache_leaf_shapes == ((4, 128), (4, 128))
    assert "bf16[4,6144,16,4,128]" in text and " copy(bf16[4,6144,16,4,128]" not in text
    assert "s8[16,64,2048,1536]" in text  # the whole stack, an argument
    assert not re.search(r"s8\[(1,)?64,2048,1536\]", text)  # never a layer's slice of it
    assert "bf16[14,64,2,2048]" in text and not re.search(r"bf16\[14,64,2,2048\]\S* copy\(", text)
    for scope in ("mtpu.conv_mix", "mtpu.expert_scan", "mtpu.expert_dispatch", "mtpu.router",
                  "mtpu.attention", "mtpu.dense_mlp"):
        assert scope in text
    kernels = _kernel_scopes(text)  # the plan's forms: the routed layers' kernel, no other
    assert len(kernels) == 8 and all(k.endswith("mtpu.expert_scan/pallas_call") for k in kernels)
    assert "bf16[1216,2048]" in text and "f32[1216,2048]" in text  # the tiles' rows in and out
    assert "f32[4289,2048]" not in text and "f32[1217,2048]" not in text  # no zeroed row buffer


def test_lfm2_widest_prefill_call_compiles_at_head_width_64_on_a_v5e(lfm2):
    """The 4 x 256 bucket call (the cell's prompts end at 256): the flash
    kernel at head width 64, the convolution as shifted sums, 4096 pairs
    through 128-row tiles, one scatter of 4 rows into the window leaf in
    place."""
    compiled = lfm2["prefill"](256)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= lfm2["page_bytes"] + lfm2["state_bytes"]
    assert mem.temp_size_in_bytes < 1 * GIB
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) < 12 * GIB
    text = compiled.as_text()
    assert "tpu_custom_call" in text  # the flash kernel went through Mosaic
    assert "mtpu.conv_mix" in text and "mtpu.expert_scan" in text


# -- SmallThinker-21BA3B's first 16 layers at their published widths (PR 41) -----------------


@pytest.fixture(scope="module")
def smallthinker(one_chip):
    """The engine's decode block and a chunk call for the benchmark's
    configuration (32 slots, 16384 pages of 16 for the 4 global layers, 8225
    for the 12 window layers' rings, 8192 positions, every expert of 16
    layers in int8, the head bf16), as shapes on the described chip: nothing
    is allocated."""
    import jax
    import jax.numpy as jnp

    from modal_examples_tpu.models import smallthinker as L
    from modal_examples_tpu.models.quantize import quantize_llama
    from modal_examples_tpu.serving.engine import LLMEngine

    cfg = L.SmallThinkerConfig.from_hf_config(
        "benchmarks/serving/configs/smallthinker-21b-a3b-int8-1chip.json"
    )
    slots, n_pages, n_window_pages, page_size, pages_per_slot, ring = 32, 16384, 8225, 16, 512, 257
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    plain = jax.eval_shape(lambda k: L.init_params(k, cfg), jax.random.PRNGKey(0))
    quantised = jax.eval_shape(
        lambda k: quantize_llama(L.init_params(k, cfg), cfg.quant_targets), jax.random.PRNGKey(0)
    )
    quantised["lm_head"] = plain["lm_head"]  # the benchmark's tree keeps the head bf16
    params = jax.tree.map(lambda a: S(a.shape, a.dtype), quantised)
    pages = S((cfg.n_cache_layers, n_pages, page_size, 4, 128), jnp.bfloat16)
    ring_pages = S((12, n_window_pages, page_size, 4, 128), jnp.bfloat16)
    state = (ring_pages, ring_pages)
    eng = object.__new__(LLMEngine)  # the two program bodies, without an engine's arrays
    eng._model, eng.cfg, eng.mesh, eng._attn_impl = L, cfg, None, "flash"
    eng.paged_impl, eng.scatter_impl = None, "xla"
    eng._block_counts, eng.decode_block = ("expert_tile_rows",), 8
    eng._runtime_offset, eng._chunk_jits = True, {}
    i32 = lambda *s: S(s, jnp.int32)  # noqa: E731
    f32 = lambda *s: S(s, jnp.float32)  # noqa: E731
    key = S((2,), jnp.uint32)

    def block():
        B = slots
        return jax.jit(
            eng._decode_block_fn, donate_argnums=(1, 2), donate_argnames=("state",)
        ).lower(
            params, pages, pages, i32(B), i32(B), S((B,), bool), i32(B),
            i32(B, pages_per_slot), S((B,), bool), key, f32(B), f32(B), i32(B), i32(B),
            state=state, window_tables=i32(B, ring),
        ).compile()

    def chunk(prefix):
        return eng._chunk_jit(prefix).lower(
            params, i32(1, 2048), pages, pages, i32(1, pages_per_slot), i32(1),
            key, f32(1), f32(1), i32(1), i32(1), i32(1), state=state,
            slot_ids=i32(1), q_offset=S((), jnp.int32), window_tables=i32(1, ring), cfg=cfg,
        ).compile()

    backend, jax.default_backend = jax.default_backend, lambda: "tpu"
    try:
        yield {"cfg": cfg, "block": block, "chunk": chunk,
               "weight_bytes": sum(_nbytes(a) for a in jax.tree.leaves(params)),
               "page_bytes": 2 * _nbytes(pages), "ring_bytes": 2 * _nbytes(ring_pages)}
    finally:
        jax.default_backend = backend


def test_smallthinker_decode_block_reads_both_page_groups_in_place_on_a_v5e(smallthinker):
    """The 32-slot decode block beside 6.3 GiB of weights and 5 GiB of pages
    in two groups: both groups aliased in and out, no temporary a copy of a
    paged leaf or of a layer's experts (``[64, 2560, 768]`` int8 is 126 MB a
    matrix). With nothing set the plan picks the ragged kernel's all-heads
    form for 4 K/V heads of 128 on the chip, in both groups (PR 42): the
    scan's body is one period of four layers with eight Mosaic calls, four
    grouped matmuls under ``mtpu.expert_scan``, one attention under
    ``mtpu.attention`` and three under ``mtpu.window_attention``. **The
    view is free**: a leaf ``[L, P, 16, 4, 128]`` (tiles of 4 rows) reaches
    the kernel as ``[L, P, 64, 128]`` rows (tiles of 8) by a ``bitcast``,
    no ``copy`` of either shape; the loop's gathered chunk ``[512 = 32 slots
    x 16 pages, 16, 4, 128]`` is gone with its gathers, and nothing of the
    block sits under ``mtpu.page_gather``."""
    compiled = smallthinker["block"]()
    mem = compiled.memory_analysis()
    assert 6.2 * GIB < smallthinker["weight_bytes"] < 6.4 * GIB  # 6.7 GB
    assert smallthinker["page_bytes"] == 2 * GIB and 3.0 * GIB < smallthinker["ring_bytes"] < 3.1 * GIB
    assert mem.alias_size_in_bytes >= smallthinker["page_bytes"] + smallthinker["ring_bytes"]
    assert mem.temp_size_in_bytes < 0.25 * GIB
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) < 11.6 * GIB
    text = compiled.as_text()
    for leaf, rows in (
        ("bf16[4,16384,16,4,128]", "bf16[4,16384,64,128]"),
        ("bf16[12,8225,16,4,128]", "bf16[12,8225,64,128]"),
    ):
        assert leaf in text and f" copy({leaf}" not in text
        assert not _relaid_out(text, leaf) and not _relaid_out(text, rows)
        assert re.search(re.escape(rows) + r"\{[^}]*T\(8,128\)\(2,1\)\} bitcast\(", text)
    assert "bf16[512,16,4,128]" not in text  # the loop's gathered chunk
    assert "s8[16,64,2560,768]" in text  # the whole stack, an argument
    assert not re.search(r"s8\[(1,)?64,2560,768\]", text)  # never a layer's slice of it
    kernels = [k.rpartition("closed_call/")[2] for k in _kernel_scopes(text)]
    assert sorted(kernels) == sorted(
        ["mtpu.expert_scan/pallas_call"] * 4 + ["mtpu.attention/pallas_call"]
        + ["mtpu.window_attention/pallas_call"] * 3
    )
    assert "mtpu.page_gather" not in text


def test_smallthinker_third_chunk_call_compiles_under_the_window_on_a_v5e(smallthinker):
    """The cell's third chunk call (2048 rows at a run-time offset over a
    prefix bucket of 4096): a global layer's flash call over 6144 keys with
    ``k_first`` in SMEM, a window layer's over a window's worth of the ring
    and the chunk with a k grid that starts late; both groups written in
    place."""
    compiled = smallthinker["chunk"](4096)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= smallthinker["page_bytes"] + smallthinker["ring_bytes"]
    assert mem.temp_size_in_bytes < 1 * GIB
    text = compiled.as_text()
    assert "mtpu.window_attention" in text and "mtpu.attention" in text
    assert "mtpu.page_gather" in text and "mtpu.expert_scan" in text
    for leaf in ("bf16[4,16384,16,4,128]", "bf16[12,8225,16,4,128]"):
        assert f" copy({leaf}" not in text
