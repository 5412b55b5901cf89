"""The lowered text (StableHLO, no locations) of the serving programs of the
families that were there before a change, at tiny sizes on the CPU, as
hashes: ``decode_step`` itself, and the engine's decode block, bucketed
prefill call and chunk call. A test pins them to the parent commit's
(``tests/test_glm_dsa.py``, as PR 31's argument counts): a model that declares
no third paged leaf and no run-time chunk offset gets the programs it had.

    python tests/lowered_text.py     # prints the table, for pinning
"""

import hashlib
import json


def _engine(family):
    import jax.numpy as jnp

    from modal_examples_tpu.models import deepseek_v2, glm_dsa, granite_hybrid, lfm2, llama
    from modal_examples_tpu.serving import LLMEngine

    cfg = {
        "llama": lambda: llama.LlamaConfig.tiny(),
        "llama_moe": lambda: llama.LlamaConfig.tiny_moe(),
        "lfm2": lambda: lfm2.Lfm2Config.tiny(),
        "deepseek_v2": lambda: deepseek_v2.DeepseekV2Config.tiny(n_held_experts=8, expert_offset=4),
        "granite_hybrid": lambda: granite_hybrid.GraniteHybridConfig.tiny(),
        "glm_dsa": lambda: glm_dsa.GlmDsaConfig.tiny(n_held_experts=8, expert_offset=4),
    }[family]()
    extra = {"enable_prefix_cache": False} if family in ("granite_hybrid", "lfm2") else {}
    return LLMEngine(
        cfg, max_slots=4, page_size=8, max_model_len=64, prefill_buckets=(16,),
        prefill_batch=2, decode_block=4, kv_dtype=jnp.bfloat16, seed=0, **extra,
    )


def hashes(family: str) -> dict:
    """name -> sha256 of the program's lowered text."""
    import jax
    import jax.numpy as jnp

    eng = _engine(family)
    try:
        cfg, B, pp = eng.cfg, eng.max_slots, eng.pages_per_slot
        i32 = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
        f32 = lambda *s: jnp.ones(s, jnp.float32)  # noqa: E731
        flags = jnp.zeros((B,), bool)
        kp, vp = eng.cache.k_pages, eng.cache.v_pages
        state = eng._state_args()
        texts = {
            "decode_step": jax.jit(
                lambda p, k, v, *a, **kw: cfg.model.decode_step(p, *a[:2], k, v, *a[2:], cfg, **kw)
            ).lower(eng.params, kp, vp, i32(B), i32(B), i32(B, pp), flags, **state).as_text(),
            "block": eng._block_jit.lower(
                eng.params, kp, vp, i32(B), i32(B), flags, i32(B), i32(B, pp), flags,
                eng._next_key(), f32(B), f32(B), i32(B), i32(B), **state,
            ).as_text(),
            "bucket": eng._prefill_jit((16, 2)).lower(
                eng.params, kp, vp, i32(2, 16), i32(2, pp), i32(2), eng._next_key(), f32(2),
                f32(2), i32(2), i32(2), **eng._state_args([0, 1], 2),
            ).as_text(),
            "chunk": eng._chunk_jit(16).lower(
                eng.params, i32(1, 16), kp, vp, i32(1, pp), i32(1), eng._next_key(), f32(1),
                f32(1), i32(1), i32(1), i32(1), **eng._state_args([0], 1),
                **({"q_offset": jnp.int32(16)} if eng._runtime_offset else {}), cfg=cfg,
            ).as_text(),
        }
    finally:
        eng.stop()
    return {name: hashlib.sha256(text.encode()).hexdigest()[:16] for name, text in texts.items()}


FAMILIES = ("llama", "llama_moe", "deepseek_v2", "granite_hybrid", "glm_dsa", "lfm2")

if __name__ == "__main__":
    print(json.dumps({f: hashes(f) for f in FAMILIES}, indent=1))
