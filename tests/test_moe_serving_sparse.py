"""A routed llama model's serving programs (``prefill``, ``prefill_chunk`` at
an offset, ``decode_step``, ``verify_step``) run ``moe.moe_swiglu_routed``:
only the pairs the router chose, out of the experts' whole stacks. Each is
held, logit by logit, against ``forward`` through ``moe_swiglu_nodrop`` (every
expert on every token, float32 ``highest``), at T = 1, 16 and wider than one
tile, with an expert no token reaches and with int8 expert leaves; and the
lowered decode program may hold no slice of a layer's whole expert stack."""

import re

import chunk_tail
import numpy as np
import pytest

ATOL = 2e-4
PS, STARVED = 16, 3  # page size; the expert the ``starved`` variant keeps dry


@pytest.fixture(scope="module")
def jax(jax_cpu):
    return jax_cpu


@pytest.fixture(scope="module")
def llama():
    from modal_examples_tpu.models import llama

    return llama


def _cfg(llama, **over):
    kw = dict(
        vocab_size=64, dim=32, n_layers=2, n_heads=2, n_kv_heads=2, ffn_dim=64,
        max_seq_len=1024, dtype="float32", n_experts=4, top_k_experts=2,
    )
    return llama.LlamaConfig(**{**kw, **over})


def _model(jax, llama, variant):
    """(cfg, params). ``starved``: channel 0 of the residual stream is held
    at 1 (the embedding sets it, nothing writes it) and the router weighs it
    -1000 for one expert, which therefore no token reaches, in either path."""
    import jax.numpy as jnp

    from modal_examples_tpu.models.quantize import quantize_llama

    cfg = _cfg(llama)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    if variant == "starved":
        lay = dict(params["layers"])
        lay["wo"] = lay["wo"].at[:, :, 0].set(0.0)
        lay["moe_down"] = lay["moe_down"].at[:, :, :, 0].set(0.0)
        lay["router"] = lay["router"].at[:, 0, :].set(0.0).at[:, 0, STARVED].set(-1000.0)
        params = dict(params, layers=lay, embed=params["embed"].at[:, 0].set(1.0))
    if variant == "int8":
        params = quantize_llama(params)
        assert params["layers"]["moe_up"].q.dtype == jnp.int8
    return cfg, params


@pytest.fixture(scope="module", params=["plain", "starved", "int8"])
def model(request, jax, llama):
    return _model(jax, llama, request.param)


def _truth(jax, llama, params, tokens, cfg):
    with jax.default_matmul_precision("highest"):
        return np.asarray(llama.forward(params, tokens, cfg, attn_impl="xla"))


def _pages(jax, cfg, B, positions):
    import jax.numpy as jnp

    pps = -(-positions // PS)
    shape = (cfg.n_layers, 1 + B * pps, PS, cfg.n_kv_heads, cfg.head_dim)
    tables = (1 + jnp.arange(B * pps, dtype=jnp.int32)).reshape(B, pps)
    return jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32), tables


def _tokens(jax, cfg, B, S, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (B, S), 1, cfg.vocab_size)


def test_the_starved_expert_is_dry(jax, llama):
    """The premise of the ``starved`` variant, at the router the programs run."""
    import jax.numpy as jnp

    from modal_examples_tpu.models import moe

    cfg, params = _model(jax, llama, "starved")
    x = jnp.ones((64, cfg.dim)).at[:, 1:].set(
        jax.random.normal(jax.random.PRNGKey(5), (64, cfg.dim - 1)) * 3
    )
    scores = jax.nn.softmax(x @ params["layers"]["router"][0], axis=-1)
    _, ids = moe.route_group_limited(scores, cfg.top_k_experts, renormalize=True)
    assert STARVED not in np.asarray(ids)


@pytest.mark.parametrize("B,S", [(1, 16), (2, 192)], ids=["T16", "T384"])
def test_prefill_matches_forward(jax, llama, model, B, S):
    import jax.numpy as jnp

    cfg, params = model
    tokens = _tokens(jax, cfg, B, S)
    k, v, tables = _pages(jax, cfg, B, S)
    with jax.default_matmul_precision("highest"):
        logits, _, _ = llama.prefill(
            params, tokens, k, v, tables, jnp.full((B,), S, jnp.int32), cfg,
            attn_impl="xla",
        )
    want = _truth(jax, llama, params, tokens, cfg)[:, -1]
    np.testing.assert_allclose(np.asarray(logits), want, atol=ATOL)


@pytest.mark.parametrize("C", [16, 384], ids=["T16", "T384"])
def test_prefill_chunk_at_an_offset_matches_forward(jax, llama, model, C):
    import jax.numpy as jnp

    cfg, params = model
    off = PS
    tokens = _tokens(jax, cfg, 1, off + C, seed=2)
    k, v, tables = _pages(jax, cfg, 1, off + C)
    with jax.default_matmul_precision("highest"):
        _, k, v = llama.prefill_chunk(
            params, tokens[:, :off], k, v, tables, jnp.asarray([off], jnp.int32),
            cfg, q_offset=0, attn_impl="xla",
        )
        logits, _, _ = llama.prefill_chunk(
            params, tokens[:, off:], k, v, tables, jnp.asarray([C], jnp.int32),
            cfg, q_offset=off, attn_impl="xla",
        )
    want = _truth(jax, llama, params, tokens, cfg)[:, -1]
    np.testing.assert_allclose(np.asarray(logits), want, atol=ATOL)


def _prefilled(jax, llama, cfg, params, B, S, room):
    import jax.numpy as jnp

    prompt = _tokens(jax, cfg, B, S, seed=3)
    k, v, tables = _pages(jax, cfg, B, S + room)
    with jax.default_matmul_precision("highest"):
        _, k, v = llama.prefill(
            params, prompt, k, v, tables, jnp.full((B,), S, jnp.int32), cfg,
            attn_impl="xla",
        )
    return prompt, k, v, tables


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("B", [1, 16], ids=["T1", "T16"])
def test_decode_step_matches_forward(jax, llama, model, B, impl):
    import jax.numpy as jnp

    cfg, params = model
    S, steps = 8, 2
    prompt, k, v, tables = _prefilled(jax, llama, cfg, params, B, S, steps)
    chain = _tokens(jax, cfg, B, steps, seed=4)
    active = jnp.ones((B,), bool).at[B - 1].set(B == 1)  # a dead slot beside the live ones
    got, pairs = [], 0
    with jax.default_matmul_precision("highest"):
        for t in range(steps):
            logits, k, v, counts = llama.decode_step(
                params, chain[:, t], jnp.full((B,), S + t, jnp.int32), k, v, tables,
                active, cfg, impl=impl, return_counts=True,
            )
            got.append(np.asarray(logits))
            pairs += np.asarray(counts)
    want = _truth(jax, llama, params, jnp.concatenate([prompt, chain], axis=1), cfg)
    live = np.asarray(active)
    for t in range(steps):
        np.testing.assert_allclose(got[t][live], want[live, S - 1 + t + 1], atol=ATOL)
    # every pair of a live slot is on an expert held here: the model holds all
    n = int(live.sum()) * steps * cfg.n_layers * cfg.top_k_experts
    assert pairs.tolist() == [n, n]


@pytest.mark.parametrize("B,Tc", [(1, 1), (4, 4)], ids=["T1", "T16"])
def test_verify_step_matches_forward(jax, llama, model, B, Tc):
    import jax.numpy as jnp

    cfg, params = model
    S = 8
    prompt, k, v, tables = _prefilled(jax, llama, cfg, params, B, S, Tc)
    chain = _tokens(jax, cfg, B, Tc, seed=4)
    with jax.default_matmul_precision("highest"):
        logits, _, _ = llama.verify_step(
            params, chain, jnp.full((B,), S, jnp.int32), k, v, tables,
            jnp.ones((B,), bool), cfg,
        )
    want = _truth(jax, llama, params, jnp.concatenate([prompt, chain], axis=1), cfg)
    np.testing.assert_allclose(np.asarray(logits), want[:, S:], atol=ATOL)


# -- what the compiled programs may not hold -------------------------------------


def _stack_slices(text: str, cfg) -> list[str]:
    """Tensors in a lowered program shaped like one layer's whole expert
    stack, [E, D, F] or [E, F, D], with or without a leading 1."""
    E, D, F = cfg.n_experts, cfg.dim, cfg.ffn_dim
    return re.findall(rf"tensor<(?:1x)?{E}x(?:{D}x{F}|{F}x{D})x\w+>", text)


@pytest.fixture(scope="module")
def mixtral_shaped(jax, llama):
    """Mixtral's proportions at a tiny size: 8 experts, 2 a token, int8."""
    from modal_examples_tpu.models.quantize import quantize_llama

    cfg = _cfg(llama, n_layers=3, n_experts=8, ffn_dim=96)
    params = jax.eval_shape(
        lambda k: quantize_llama(llama.init_params(k, cfg)), jax.random.PRNGKey(0)
    )
    return cfg, params


def _lower(jax, fn, *args):
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_decode_program_slices_no_layers_expert_stack(jax, llama, mixtral_shaped, impl):
    import jax.numpy as jnp

    cfg, params = mixtral_shaped
    B = 16
    k, v, tables = _pages(jax, cfg, B, 64)
    text = _lower(
        jax,
        lambda p, tok, pos, k, v, act: llama.decode_step(
            p, tok, pos, k, v, tables, act, cfg, impl=impl
        ),
        params, jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32), k, v,
        jnp.ones((B,), bool),
    )
    assert "mtpu.expert_dispatch" in text  # the routed layer is in there
    assert _stack_slices(text, cfg) == []


@pytest.mark.parametrize("program", ["prefill", "prefill_chunk", "verify_step"])
def test_prefill_and_verify_programs_slice_no_layers_expert_stack(
    jax, llama, mixtral_shaped, program
):
    import jax.numpy as jnp

    cfg, params = mixtral_shaped
    B, S = 2, 32
    k, v, tables = _pages(jax, cfg, B, 2 * S)
    tokens, lens = jnp.zeros((B, S), jnp.int32), jnp.full((B,), S, jnp.int32)
    fn = {
        "prefill": lambda p, k, v: llama.prefill(
            p, tokens, k, v, tables, lens, cfg, attn_impl="xla"
        ),
        "prefill_chunk": lambda p, k, v: llama.prefill_chunk(
            p, tokens, k, v, tables, lens, cfg, q_offset=S, attn_impl="xla"
        ),
        "verify_step": lambda p, k, v: llama.verify_step(
            p, tokens[:, :4], lens, k, v, tables, jnp.ones((B,), bool), cfg
        ),
    }[program]
    assert _stack_slices(_lower(jax, fn, params, k, v), cfg) == []


def test_the_search_finds_the_fault_where_it_is(jax, llama, mixtral_shaped):
    """The control: ``forward`` still scans over every leaf of a layer, the
    experts among them, and the search has to see that."""
    import jax.numpy as jnp

    cfg, params = mixtral_shaped
    text = _lower(
        jax, lambda p: llama.forward(p, jnp.zeros((1, 8), jnp.int32), cfg, attn_impl="xla"),
        params,
    )
    assert _stack_slices(text, cfg)


# -- the tile, from the shapes of a call -------------------------------------------


@pytest.mark.parametrize(
    "tokens,top_k,held,want",
    [
        # a decode step whose pairs crowd the experts: every token in one tile an expert
        (1, 2, 8, 16), (16, 2, 8, 16), (100, 2, 4, 112),
        # ... and one whose pairs spread to a few an expert: four times their mean, in 16-row vregs
        (64, 4, 64, 16), (62, 4, 64, 16), (16, 6, 40, 16), (16, 8, 16, 16), (4, 4, 64, 16),
        (64, 2, 8, 64), (100, 2, 8, 112), (96, 4, 64, 32), (127, 8, 256, 16),
        # the chip's measured best at every wider T, whatever the spread
        (128, 2, 8, 128), (2048, 2, 8, 128), (4 * 2048, 2, 8, 128), (128, 4, 64, 128),
    ],
)
def test_tile_follows_the_calls_shape(tokens, top_k, held, want):
    from modal_examples_tpu.models import moe

    assert moe.expert_tile(tokens, top_k, held) == want


@pytest.mark.parametrize("tile", [16, 32, 128])
def test_any_tile_gives_the_same_sum(jax, tile):
    """The tile is a matter of speed: the result does not depend on it."""
    import jax.numpy as jnp

    from modal_examples_tpu.models import moe

    T, D, F, E, k = 96, 16, 32, 4, 2
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    gate, up = (jax.random.normal(a, (E, D, F)) * D**-0.5 for a in ks[:2])
    down = jax.random.normal(ks[2], (E, F, D)) * F**-0.5
    x = jax.random.normal(ks[3], (T, D))
    router = jax.random.normal(ks[4], (D, E))
    with jax.default_matmul_precision("highest"):
        want, _ = moe.moe_swiglu_nodrop(router, gate, up, down, x, k)
        scores = jax.nn.softmax(x @ router, axis=-1)
        w, ids = moe.route_group_limited(scores, k, renormalize=True)
        got, counts = moe.moe_swiglu_sparse(gate, up, down, x, ids, w, tile=tile)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert counts.tolist() == [T * k, T * k]


# -- the counter that says the mechanism ran ----------------------------------------


def test_a_routed_llama_engine_counts_its_decode_pairs(jax, llama):
    from modal_examples_tpu.observability import catalog as C
    from modal_examples_tpu.serving import LLMEngine, SamplingParams
    from modal_examples_tpu.utils.prometheus import default_registry

    def value(where):
        return default_registry.value(C.ROUTED_PAIRS_TOTAL, {"where": where}) or 0.0

    assert not llama.LlamaConfig.tiny().counts_routed_pairs
    cfg = llama.LlamaConfig.tiny_moe()
    assert cfg.counts_routed_pairs
    before = value("held"), value("elsewhere")
    eng = LLMEngine(cfg, max_slots=2, max_model_len=64, prefill_buckets=(32,), seed=0)
    try:
        eng.generate("the pairs the router chose", SamplingParams(max_tokens=12, temperature=0.0))
    finally:
        eng.stop()
    held, elsewhere = value("held") - before[0], value("elsewhere") - before[1]
    per_step = cfg.n_layers * cfg.top_k_experts  # one live slot
    assert held >= eng.decode_block * per_step and held % per_step == 0
    assert elsewhere == 0  # the router is no wider than what the chip holds


# -- the routed layer under a tail chunk of each width --------------------------------


@pytest.fixture(scope="module")
def tail_engine(jax, llama):
    from modal_examples_tpu.serving import LLMEngine

    eng = LLMEngine(
        llama.LlamaConfig.tiny_moe(), max_slots=2, max_model_len=chunk_tail.MAX_MODEL_LEN,
        page_size=16, prefill_buckets=chunk_tail.BUCKETS, seed=0,
    )
    yield chunk_tail.warmed(eng)
    eng.stop()


@pytest.mark.parametrize("case", list(chunk_tail.CASES))
def test_the_routed_tail_chunk_is_as_wide_as_what_is_left(tail_engine, case, monkeypatch):
    """Fewer rows reach the expert tiles of the last chunk call; the pairs of
    the real tokens are the same ones (tests/chunk_tail.py)."""
    chunk_tail.check(tail_engine, case, monkeypatch)
