#!/usr/bin/env python3
"""The parts of the learned sparse attention alone, at the GLM-5.2 cell's
geometry: masked-dense against gathered selection, for prefill and decode.

    chiprun -- python benchmarks/sparse_micro.py [--prefix 10240] [--context 12288]

ms a call (bf16, one layer) of

- prefill, a 2048-query chunk over ``--prefix`` cached positions: the index
  scores (``ops.sparse_attention.index_scores``), the selection as a mask by
  bisection (``select_mask``) against ``lax.top_k`` over the same scores,
  the blocked expansion of the latents, the flash kernel under the mask
  (``selected_attention``: masked-dense, what the program runs) against a
  **gathered** form written here in plain XLA (the selected latents of a
  block of queries gathered, the absorbed attention over them);
- decode, 16 sequences of ``--context`` cached positions: the index scores
  over the third leaf (``paged_index_scores``), ``select_positions``, the
  absorbed attention over the gathered selection
  (``paged_latent_decode_attention_selected``: gathered, what the program
  runs) against the chunked loop over every live page under a keep mask
  written here (masked-dense).

PERF.md section 6 (PR 34) has the readings that chose the forms. Needs the
chip: a time from the interpreter says nothing.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

H, RANK, ROPE, NOPE, VD = 64, 512, 64, 192, 256  # GLM-5.2's attention
HI, DI, TOPK, C = 32, 128, 2048, 2048  # its indexer, and a chunk's queries
SLOTS, PAGE, N_PAGES = 16, 16, 24576


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--prefix", type=int, default=10240)
    ap.add_argument("--context", type=int, default=12288)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default="chiprun_out/sparse_micro.json")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        print("sparse_micro: no TPU; the interpreter's times mean nothing", file=sys.stderr)
        return 1

    from modal_examples_tpu.ops import sparse_attention as sp
    from modal_examples_tpu.ops.paged_attention import decode_chunk_pages

    dt = jnp.bfloat16
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 32))

    def rand(*shape, dtype=dt):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    def timed(name, fn, *a):
        fn = jax.jit(fn)
        out = jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(args.reps):
            out = fn(*a)
        jax.block_until_ready(out)
        ms = 1000.0 * (time.perf_counter() - t0) / args.reps
        results[name] = ms
        print(f"{name:44s} {ms:9.3f} ms", flush=True)
        return out

    results: dict = {"prefix": args.prefix, "context": args.context}
    scale = (NOPE + ROPE) ** -0.5

    # -- prefill ---------------------------------------------------------------------
    S = args.prefix + C
    q_idx, w, k_idx = rand(1, C, HI, DI), rand(1, C, HI, dtype=jnp.float32), rand(1, S, DI)
    allowed = (jnp.arange(S)[None, None, :] <= args.prefix + jnp.arange(C)[None, :, None])
    scores = timed("prefill index_scores", sp.index_scores, q_idx, w, k_idx)
    mask = timed("prefill select_mask (bisection)", lambda s: sp.select_mask(s, allowed, TOPK), scores)
    idx = timed(
        "prefill lax.top_k",
        lambda s: jax.lax.top_k(jnp.where(allowed, s, -jnp.inf), TOPK)[1], scores,
    )
    same = jnp.zeros((1, C, S), bool).at[0, jnp.arange(C)[:, None], idx[0]].set(True)
    results["mask_is_top_k"] = bool(jnp.all(same == mask))
    c_all, r_all = rand(1, S, RANK), rand(1, S, ROPE)
    wkv = rand(RANK, H * (NOPE + VD)) * RANK ** -0.5
    block = sp.key_block(S)

    def expand(c, r):
        def one(cr):
            cb, rb = cr
            kv = jnp.dot(cb, wkv, preferred_element_type=jnp.float32).astype(dt)
            kv = kv.reshape(1, block, H, NOPE + VD)
            k = jnp.concatenate(
                [kv[..., :NOPE], jnp.broadcast_to(rb[:, :, None, :], (1, block, H, ROPE))], -1)
            return k.transpose(0, 2, 1, 3), kv[..., NOPE:].transpose(0, 2, 1, 3)

        blocks = lambda a: a.reshape(1, S // block, block, -1).transpose(1, 0, 2, 3)  # noqa: E731
        k, v = jax.lax.map(one, (blocks(c), blocks(r)))
        return k.transpose(1, 0, 2, 3, 4), v.transpose(1, 0, 2, 3, 4)

    k, v = timed("prefill expand latents (blocked)", expand, c_all, r_all)
    q = rand(1, H, C, NOPE + ROPE)
    dense = timed(
        "prefill attention masked-dense (flash)",
        lambda *a: sp.selected_attention(*a, sm_scale=scale), q, k, v, mask,
    )
    causal = timed(
        "prefill attention every position (flash)",
        lambda *a: sp.selected_attention(*a, sm_scale=scale), q, k, v, allowed,
    )
    del causal

    wk = wkv.reshape(RANK, H, NOPE + VD)[..., :NOPE]
    wv = wkv.reshape(RANK, H, NOPE + VD)[..., NOPE:]

    def gathered(q, c, r, idx):
        """The absorbed attention of a block of queries over their own
        ``TOPK`` gathered latents, 128 queries at a time."""
        q_lat = jnp.einsum("hcd,rhd->chr", q[0, :, :, :NOPE], wk, preferred_element_type=jnp.float32)
        q_pe = q[0, :, :, NOPE:].transpose(1, 0, 2)

        def rows(a):
            ql, qp, ix = a  # [b, H, RANK], [b, H, ROPE], [b, TOPK]
            cs, rs = c[0][ix], r[0][ix]  # [b, TOPK, .]
            s = (jnp.einsum("qhc,qkc->qhk", ql.astype(dt), cs, preferred_element_type=jnp.float32)
                 + jnp.einsum("qhr,qkr->qhk", qp, rs, preferred_element_type=jnp.float32)) * scale
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("qhk,qkc->qhc", p.astype(dt), cs, preferred_element_type=jnp.float32)
            return jnp.einsum("qhc,chd->qhd", o.astype(dt), wv, preferred_element_type=jnp.float32)

        b = 128
        out = jax.lax.map(rows, (q_lat.reshape(C // b, b, H, RANK), q_pe.reshape(C // b, b, H, ROPE),
                                 idx[0].reshape(C // b, b, TOPK)))
        return out.reshape(C, H, VD).transpose(1, 0, 2)[None].astype(dt)

    got = timed("prefill attention gathered (XLA, absorbed)", gathered, q, c_all, r_all, idx)
    results["prefill_forms_max_diff"] = float(jnp.abs(got.astype(jnp.float32) - dense.astype(jnp.float32)).max())
    del k, v, got, dense, scores, mask, same

    # -- decode -----------------------------------------------------------------------
    pages_per_seq = -(-(args.context + 8) // PAGE)
    c_pages, r_pages, i_pages = rand(1, N_PAGES, PAGE, 1, RANK), rand(1, N_PAGES, PAGE, 1, ROPE), rand(1, N_PAGES, PAGE, 1, DI)
    tables = (1 + jnp.arange(SLOTS * pages_per_seq, dtype=jnp.int32)).reshape(SLOTS, pages_per_seq)
    lens = jnp.full((SLOTS,), args.context, jnp.int32)
    qi, wi, ki = rand(SLOTS, HI, DI), rand(SLOTS, HI, dtype=jnp.float32), rand(SLOTS, DI)
    layer = jnp.int32(0)
    dscores = timed(
        "decode paged_index_scores",
        lambda *a: sp.paged_index_scores(*a), qi, wi, i_pages, layer, tables, lens, ki,
    )
    sel, counts = timed("decode select_positions (lax.top_k)", lambda s: sp.select_positions(s, TOPK), dscores)
    q_lat, q_pe, c_new, r_new = rand(SLOTS, H, RANK), rand(SLOTS, H, ROPE), rand(SLOTS, RANK), rand(SLOTS, ROPE)
    o_sel = timed(
        "decode attention gathered",
        lambda *a: sp.paged_latent_decode_attention_selected(*a, sm_scale=scale),
        q_lat, q_pe, c_pages, r_pages, layer, tables, sel, counts, lens, c_new, r_new,
    )
    keep = jnp.zeros((SLOTS, pages_per_seq * PAGE), bool).at[jnp.arange(SLOTS)[:, None], sel].set(counts)

    def masked_dense(q_lat, q_pe, c_pages, r_pages, tables, keep, lens, c_new, r_new):
        """``paged_latent_decode_attention_chunked``'s loop over every live
        page, the positions outside the selection masked."""
        W = decode_chunk_pages(PAGE, pages_per_seq)
        pad = -pages_per_seq % W
        tables_, keep_ = jnp.pad(tables, ((0, 0), (0, pad))), jnp.pad(keep, ((0, 0), (0, pad * PAGE)))
        span = W * PAGE
        trips = (jnp.max(lens) + span - 1) // span
        masked = -0.7 * float(jnp.finfo(jnp.float32).max)

        def trip(c, carry):
            m, l, acc = carry
            cols = jax.lax.dynamic_slice_in_dim(tables_, c * W, W, axis=1)
            cs = c_pages[0, cols].reshape(SLOTS, span, RANK)
            rs = r_pages[0, cols].reshape(SLOTS, span, ROPE)
            s = (jnp.einsum("bhc,bkc->bhk", q_lat, cs, preferred_element_type=jnp.float32)
                 + jnp.einsum("bhr,bkr->bhk", q_pe, rs, preferred_element_type=jnp.float32)) * scale
            pos = c * span + jnp.arange(span)
            ok = (pos[None] < lens[:, None]) & jax.lax.dynamic_slice_in_dim(keep_, c * span, span, 1)
            s = jnp.where(ok[:, None], s, masked)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            acc = acc * alpha[..., None] + jnp.einsum("bhk,bkc->bhc", p.astype(dt), cs, preferred_element_type=jnp.float32)
            return m_new, l * alpha + p.sum(-1), acc

        own = jnp.take_along_axis(keep_, lens[:, None], axis=1)
        s_new = (jnp.einsum("bhc,bc->bh", q_lat, c_new, preferred_element_type=jnp.float32)
                 + jnp.einsum("bhr,br->bh", q_pe, r_new, preferred_element_type=jnp.float32)) * scale
        s_new = jnp.where(own, s_new, masked)
        acc0 = jnp.broadcast_to(c_new.astype(jnp.float32)[:, None, :], (SLOTS, H, RANK))
        m, l, acc = jax.lax.fori_loop(0, trips, trip, (s_new, jnp.ones_like(s_new), acc0))
        return acc / l[..., None]

    o_all = timed("decode attention masked-dense (every live page)", masked_dense,
                  q_lat, q_pe, c_pages, r_pages, tables, keep, lens, c_new, r_new)
    results["decode_forms_max_diff"] = float(jnp.abs(o_all - o_sel).max())
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(results, indent=1))
    print(json.dumps(results))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
