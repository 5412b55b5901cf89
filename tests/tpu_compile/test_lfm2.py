import re

import pytest

from .helpers import GIB, _kernel_scopes, _nbytes


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

