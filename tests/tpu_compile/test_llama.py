"""The llama-family cells (Mistral, Mixtral) and DeepSeek-V2 beside them: the
docqa cells' flash chunk calls and tail chunk programs, the decode block."""

import pytest

from .helpers import GIB, _relaid_out


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

