"""A chunked prefill's first token comes out of the chunk program
(``LLMEngine._chunk_jit``: every chunk samples, the last one's token is the
request's first): it is the token eager ``sample()`` draws on the model's own
last-token logits with the key, seed and position the engine gave the eager
call before, whichever way the prompt reached its last chunk, and a chunked
prompt still takes one key off the engine's stream."""

import numpy as np
import pytest

#: the largest bucket of the engines here: prompts beyond it are chunked
C = 32
N_PROMPT = 121  # BOS + 120 letters: chunks of 32, 32, 32 and a tail of 25 (bucket 32)


@pytest.fixture(scope="module")
def jax(jax_cpu):
    return jax_cpu


def _engine(budget=0, seed=0):
    from modal_examples_tpu.models import llama
    from modal_examples_tpu.serving import LLMEngine

    return LLMEngine(
        llama.LlamaConfig.tiny(), seed=seed, max_slots=4, max_model_len=256, page_size=16,
        prefill_buckets=(16, C), max_prefill_tokens_per_tick=budget,
    )


def _prompt(draw: int, n: int = N_PROMPT) -> str:
    letters = np.random.default_rng(draw).integers(97, 123, size=n - 1)
    return bytes(letters.tolist()).decode()


def _sampling(case: str):
    from modal_examples_tpu.serving import SamplingParams

    return {
        "greedy": SamplingParams(max_tokens=2, temperature=0.0),
        "seeded": SamplingParams(max_tokens=2, temperature=0.9, top_p=0.8, top_k=12, seed=77),
        "unseeded": SamplingParams(max_tokens=2, temperature=1.0),
    }[case]


def _reference_logits(jax, eng, tokens):
    """The last-token logits of ``tokens`` by the model's own chunk calls over
    an empty cache of its own: what the engine's chunk programs returned
    before they sampled."""
    import jax.numpy as jnp

    from modal_examples_tpu.models import llama

    cfg = eng.cfg
    k_pages, v_pages = (jnp.zeros_like(a) for a in (eng.cache.k_pages, eng.cache.v_pages))
    table = np.zeros((1, eng.pages_per_slot), np.int32)
    n_pages = -(-len(tokens) // eng.cache.page_size)
    table[0, :n_pages] = 1 + np.arange(n_pages)
    logits = None
    for offset in range(0, len(tokens), C):
        chunk = tokens[offset : offset + C]
        toks = np.zeros((1, eng._bucket_for(len(chunk))), np.int32)
        toks[0, : len(chunk)] = chunk
        logits, k_pages, v_pages = jax.jit(
            lambda p, t, k, v, tab, n, off=offset: llama.prefill_chunk(
                p, t, k, v, tab, n, cfg=cfg, q_offset=off, attn_impl=eng._attn_impl
            )
        )(eng.params, toks, k_pages, v_pages, table, jnp.asarray([len(chunk)], np.int32))
    return logits


def _eager_token(jax, logits, key, params, seed: int, n_prompt: int) -> int:
    import jax.numpy as jnp

    from modal_examples_tpu.serving.sampling import sample

    return int(sample(
        logits, key, jnp.asarray([params.temperature], np.float32),
        jnp.asarray([params.top_p], np.float32), jnp.asarray([params.top_k], np.int32),
        seeds=jnp.asarray([seed], np.int32), step_ids=jnp.asarray([n_prompt], np.int32),
    )[0])


def _stream_keys(jax, seed: int, n: int):
    """The first ``n`` keys ``LLMEngine._next_key`` hands out."""
    key, keys = jax.random.PRNGKey(seed), []
    for _ in range(n):
        key, sub = jax.random.split(key)
        keys.append(sub)
    return keys


def _first_token(eng, how: str, prompt: str, params, *, forget_seed: bool) -> tuple:
    """``(request, first token)`` of ``prompt`` through the slot path driven
    by ``step()`` (``slot``) or the slot-free one (``pages``). ``forget_seed``
    takes the engine-assigned seed away, so the token hangs on the engine's
    key alone."""
    if how == "pages":
        req = eng.make_request(prompt, params)
        if forget_seed:
            req.auto_seed = None
        state = eng.prefill_sync(req)
        eng.release_claim(state["claim"])
        return req, state["first_token"]
    req = eng.submit(prompt, params)
    if forget_seed:
        req.auto_seed = None
    for _ in range(200):
        eng.step()
        if req.generated_tokens:
            return req, req.generated_tokens[0]
    raise AssertionError("no first token in 200 ticks")


@pytest.mark.parametrize("how,budget", [("slot", 0), ("slot", 1), ("slot", 40), ("pages", 0)])
@pytest.mark.parametrize("case", ["greedy", "seeded", "unseeded"])
def test_the_first_token_is_eager_sample_on_the_reference_logits(jax, case, how, budget):
    """One tick, a chunk a tick, a budget that cuts between chunks, and the
    slot-free path: the same token, the one the eager call gave."""
    from modal_examples_tpu.serving.engine import _req_seed

    eng = _engine(budget=budget, seed=5)
    try:
        params = _sampling(case)
        req, got = _first_token(eng, how, _prompt(1), params, forget_seed=False)
        assert len(req.prompt_tokens) == N_PROMPT > C
        logits = _reference_logits(jax, eng, req.prompt_tokens)
        assert logits.shape == (1, eng.cfg.vocab_size) and str(logits.dtype) == "float32"
        seed = _req_seed(req)
        assert (seed >= 0) and (seed == 77) == (case == "seeded")
        key = _stream_keys(jax, 5, 1)[0]
        assert got == _eager_token(jax, logits, key, params, seed, N_PROMPT)
    finally:
        eng.stop()


@pytest.mark.parametrize("how,budget", [("slot", 0), ("slot", 1), ("pages", 0)])
def test_two_chunked_prompts_take_one_key_each_off_the_engines_stream(jax, how, budget):
    """Neither request has a seed (-1: base keys split from the call's key),
    so each first token shows which key its last chunk was handed: the
    stream's first for the first prompt and its second for the second, as the
    eager call drew them; the chunks before the last draw none."""
    eng = _engine(budget=budget, seed=11)
    draws = []
    inner = eng._next_key
    eng._next_key = lambda: draws.append(len(draws)) or inner()
    blocks, dispatch_block = [], eng._dispatch_block
    eng._dispatch_block = lambda *a, **kw: blocks.append(a) or dispatch_block(*a, **kw)
    try:
        params = _sampling("unseeded")
        keys = _stream_keys(jax, 11, 2)
        for i, n in enumerate((N_PROMPT, 70)):  # 4 chunks, then 3
            req, got = _first_token(eng, how, _prompt(20 + i, n), params, forget_seed=True)
            if how == "slot":
                eng.abort(req)  # before a decode block draws a key of its own
                eng.step()
            assert draws == list(range(i + 1)) and not blocks
            logits = _reference_logits(jax, eng, req.prompt_tokens)
            assert got == _eager_token(jax, logits, keys[i], params, -1, n)
            other = _eager_token(jax, logits, keys[1 - i], params, -1, n)
            assert got != other or i  # the first prompt's token tells the two keys apart
    finally:
        eng.stop()
