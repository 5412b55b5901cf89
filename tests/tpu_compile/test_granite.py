import pytest

from .helpers import GIB, _kernel_scopes, _nbytes


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



# -- Granite-4.0-H-Small, one period on one chip of an EP2 layer (PR 45) -------------------------


@pytest.fixture(scope="module")
def granite_small(one_chip):
    """The engine's decode block for the benchmark's routed configuration
    (ten layers, 36 of 72 experts held, 128 state heads; 64 slots, 6144
    pages of 16), as shapes on the described chip: nothing is allocated."""
    import jax
    import jax.numpy as jnp

    from modal_examples_tpu.models import granite_hybrid as G
    from modal_examples_tpu.serving.engine import LLMEngine

    cfg = G.GraniteHybridConfig(
        vocab_size=25088, dim=4096, mamba_n_heads=128, ffn_dim=1536, n_experts=72,
        n_held_experts=36, top_k=10, expert_dim=768, attention_multiplier=0.0078125,
        logits_scaling=16.0, layer_types=("mamba",) * 5 + ("attention",) + ("mamba",) * 4,
    )
    slots, n_pages, page_size, pages_per_slot = 64, 6144, 16, 128
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(lambda k: G.init_params(k, cfg), jax.random.PRNGKey(0)),
    )
    pages = S((cfg.n_cache_layers, n_pages, page_size, *cfg.cache_leaf_shapes[0]), jnp.bfloat16)
    state = tuple(S((n, slots, *shape), jnp.dtype(dt)) for n, shape, dt in cfg.state_leaves)
    eng = object.__new__(LLMEngine)  # the program's body, without an engine's arrays
    eng._model, eng.cfg, eng.mesh, eng._attn_impl = G, cfg, None, "flash"
    eng.paged_impl, eng.scatter_impl = None, "xla"
    eng._block_counts, eng.decode_block = ("routed_pairs", "expert_tile_rows"), 8
    i32 = lambda *s: S(s, jnp.int32)  # noqa: E731
    f32 = lambda *s: S(s, jnp.float32)  # noqa: E731
    B = slots

    def block():
        return jax.jit(
            eng._decode_block_fn, donate_argnums=(1, 2), donate_argnames=("state",)
        ).lower(
            params, pages, pages, i32(B), i32(B), S((B,), bool), i32(B),
            i32(B, pages_per_slot), S((B,), bool), S((2,), jnp.uint32), f32(B), f32(B),
            i32(B), i32(B), state=state,
        ).compile()

    backend, jax.default_backend = jax.default_backend, lambda: "tpu"
    try:
        yield {"cfg": cfg, "block": block, "params_bytes": sum(map(_nbytes, jax.tree.leaves(params))),
               "state_bytes": sum(_nbytes(s) for s in state), "page_bytes": 2 * _nbytes(pages)}
    finally:
        jax.default_backend = backend


def test_the_routed_decode_block_holds_two_mosaic_calls_a_layer_and_its_state_in_place(granite_small):
    """The 64-slot decode block of one period of Granite-4.0-H-Small on one
    chip of two: 9.3 GB of bf16 weights, 2.45 GB of per-slot state (a 4 MiB
    float32 state a slot a layer at 128 heads, two head tiles of 2 MiB),
    0.4 GB of pages. Each scan over a run of Mamba layers holds the state
    step's kernel under ``mtpu.ssm_step`` (the leaf handed whole and aliased
    to it) **and** the experts' grouped matmul under ``mtpu.expert_scan`` (bf16
    experts of 4096 x 768, F in 2 blocks of 384, the ``[10, 36, 4096, 768]``
    stacks handed whole: no layer's or expert's slice is a copy); the
    attention layer holds the second alone. The state and the pages are
    updated in place, and with the arguments the temporaries fit the chip."""
    import re

    compiled = granite_small["block"]()
    mem = compiled.memory_analysis()
    held = granite_small["state_bytes"] + granite_small["page_bytes"]
    assert 9.2e9 < granite_small["params_bytes"] < 9.4e9
    assert 2.44e9 < granite_small["state_bytes"] < 2.46e9 and granite_small["page_bytes"] == 402653184
    assert mem.alias_size_in_bytes >= held  # updated in place, not copied out
    assert mem.temp_size_in_bytes < 1 * GIB
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) < 13.5 * GIB
    text = compiled.as_text()
    assert "f32[9,64,128,64,128]" in text  # the state leaf, handed whole to the kernel
    assert not re.search(r"f32\[9,64,128,64,128\]\S* copy\(", text)
    assert "f32[64,128,64,128]" not in text  # no XLA pass over a layer's state
    assert "bf16[10,36,4096,768]" in text and "bf16[10,36,768,4096]" in text
    assert not re.search(r"bf16\[(10,)?36,(4096,768|768,4096)\]\S* copy\(", text)
    assert "bf16[36,4096,768]" not in text and "bf16[4096,768]" not in text  # no slice of the stacks
    scopes = _kernel_scopes(text)
    step = [s for s in scopes if "mtpu.ssm_step" in s]
    scan = [s for s in scopes if "mtpu.expert_scan" in s]
    # two runs of Mamba layers, each a scan whose body holds both; the attention layer's experts
    assert len(step) == 2 and len(scan) == 3 and len(scopes) == 5
    for scope in ("mtpu.router", "mtpu.expert_dispatch", "mtpu.dense_mlp", "mtpu.ssm_proj",
                  "mtpu.attention"):
        assert scope in text
