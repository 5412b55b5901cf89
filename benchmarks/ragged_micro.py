#!/usr/bin/env python3
"""The decode step's attention alone, at two models' cache geometries.

    chiprun -- python benchmarks/ragged_micro.py [--shapes mistral smallthinker]
        [--parent <checkout>] [--sizes 8x8 16x16 ...]

us a call (one layer's attention of one decode step) and GB/s over the live
K/V bytes, for the ways a model's ``paged_impl_plan`` chooses between: the
chunked XLA loop and the ragged kernel (``paged_decode_attention_ragged``),
at the sizes the kernel chooses and at each ``--sizes`` CHUNKxUPDATE (pages a
half of the DMA ring x pages a softmax update), and the kernel's fetch with
no products (``products=False``: what the DMAs alone take).

``mistral``: 16 slots, 32 heads over 8 KV heads of 128, pages of 16, bf16, a
256-page table a slot over a cache of 3072 scattered pages; the loop and the
kernel in both variants. Batches: two drawn as ``reason-closed`` draws its
contexts (a prompt of 65-256 and a uniform share of an answer of 512-1024),
and a docqa batch of 12 x ~2600 with 4 dead slots. With ``--parent`` the same
through that checkout's kernel.

``smallthinker``: 32 slots, 28 heads (32 rows in the kernel) over 4 KV heads
of 128, pages of 16, bf16, the ``flat`` form only. ``ring``: a window layer,
a ring of 257 pages a slot (window 4096), every context past the window,
positions drawn as ``reason-long-closed`` draws them (a prompt of 4096-6144
and a uniform share of an answer of 768-1280), so first pages and ``starts``
fall as they do there; ``table``: a global layer, 512 pages a slot at contexts
of 4.2-7.6k.

A call is timed inside a loop of 32 so that no dispatch is in it. Every
output is compared with the dense softmax over the gathered pages
(``mistral``) or with the loop (``smallthinker``). Needs the chip: a time
from the interpreter says nothing.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

D, PAGE, LAYERS, CALLS = 128, 16, 2, 32
WINDOW = 4096
UNCHECKED = "fetch_only"  # an op of this name computes no output to compare


def _sized(pa, args, variant, page, hkv, table):
    """The kernel in ``variant`` at the sizes it chooses and at each of --sizes."""
    sizes = [pa.ragged_kernel_sizes(variant, page, hkv, D, 2, table)]
    sizes += [tuple(int(n) for n in s.split("x")) for s in args.sizes]
    return {
        f"{variant}_{chunk}x{update}" + ("_chosen" if i == 0 else ""): dict(
            variant=variant, chunk_pages=chunk, update_pages=update
        )
        for i, (chunk, update) in enumerate(sizes)
    }


def mistral(args, jax, jnp, np, pa):
    """-> (sizes, {batch: tables, lens, live positions}, ops (of every batch, or
    by batch), the reference op (or by batch))."""
    B, HQ, HKV, TABLE, N_PAGES = 16, 32, 8, 256, 3072
    rng = np.random.default_rng(35)

    def reason():
        prompt, answer = rng.integers(65, 257, B), rng.integers(512, 1025, B)
        return (prompt + rng.random(B) * answer).astype(np.int32)

    docqa = np.array([2600 + 40 * i for i in range(12)] + [0] * 4, np.int32)
    batches = {"reason_a": reason(), "reason_b": reason(), "docqa_12_of_16": docqa}
    ops = {"loop": pa.paged_decode_attention_chunked}
    for variant in ("flat", "grouped"):
        for name, kw in _sized(pa, args, variant, PAGE, HKV, TABLE).items():
            ops[name] = lambda *a, kw=kw: pa.paged_decode_attention_ragged(*a, **kw)
    ops["flat_fetch_only"] = lambda *a: pa.paged_decode_attention_ragged(
        *a, variant="flat", products=False
    )
    if args.parent:
        spec = importlib.util.spec_from_file_location(
            "modal_examples_tpu.ops._parent_paged",
            Path(args.parent) / "modal_examples_tpu/ops/paged_attention.py",
        )
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
        ops["parent_kernel"] = parent.paged_decode_attention_ragged
    # every slot its own pages, scattered as an allocator leaves them
    live = 190  # pages a slot: the longest docqa context is 3040 positions
    tables = jnp.pad(
        jnp.asarray(rng.permutation(N_PAGES - 1)[: B * live].reshape(B, live) + 1, jnp.int32),
        ((0, 0), (0, TABLE - live)),
    )

    def reference(q, kp, vp, layer, pt, lens, kn, vn):
        return pa.paged_decode_attention_inflight(q, kp[layer][pt], vp[layer][pt], lens, kn, vn)

    cases = {
        name: dict(tables=tables, lens=lens, live=int(lens.sum())) for name, lens in batches.items()
    }
    return dict(B=B, HQ=HQ, HKV=HKV, N_PAGES=N_PAGES), cases, ops, reference


def smallthinker(args, jax, jnp, np, pa):
    B, HQ, HKV = 32, 28, 4
    ring = pa.window_ring_pages(WINDOW, PAGE)  # 257
    TABLE, N_PAGES = 512, 1 + B * 512
    rng = np.random.default_rng(42)
    # where the cell's sequences stand: a prompt of 4096-6144 and a uniform
    # share of an answer of 768-1280 (contexts 4.1-7.4k, all past the window)
    positions = (
        rng.integers(4096, 6145, B) + rng.random(B) * rng.integers(768, 1281, B)
    ).astype(np.int32)
    contexts = np.linspace(4200, 7600, B).astype(np.int32)
    rng.shuffle(contexts)
    first, lens, starts = pa.window_decode_span(positions, WINDOW, PAGE, ring)
    ring_tables = jnp.asarray(1 + rng.permutation(B * ring).reshape(B, ring), jnp.int32)
    tables = jnp.asarray(1 + rng.permutation(B * TABLE).reshape(B, TABLE), jnp.int32)
    window_loop = lambda *a: pa.paged_window_decode_attention_chunked(*a, window=WINDOW)  # noqa: E731
    ops = {"ring": {"loop": window_loop}, "table": {"loop": pa.paged_decode_attention_chunked}}
    for name, kw in _sized(pa, args, "flat", PAGE, HKV, ring).items():
        ops["ring"][name] = lambda *a, kw=kw: pa.paged_window_decode_attention_ragged(
            *a, window=WINDOW, **kw
        )
    for name, kw in _sized(pa, args, "flat", PAGE, HKV, TABLE).items():
        ops["table"][name] = lambda *a, kw=kw: pa.paged_decode_attention_ragged(*a, **kw)
    ops["ring"]["flat_fetch_only"] = lambda *a: pa.paged_window_decode_attention_ragged(
        *a, window=WINDOW, variant="flat", products=False
    )
    ops["table"]["flat_fetch_only"] = lambda *a: pa.paged_decode_attention_ragged(
        *a, variant="flat", products=False
    )
    cases = {
        "ring": dict(
            tables=ring_tables, lens=positions, live=int((lens - starts).sum()),
            note=dict(first_pages=[int(first.min()), int(first.max())],
                      starts=[int(starts.min()), int(starts.max())],
                      ring_prefix=[int(lens.min()), int(lens.max())]),
        ),
        "table": dict(tables=tables, lens=contexts, live=int(contexts.sum())),
    }
    references = {"ring": window_loop, "table": pa.paged_decode_attention_chunked}
    return dict(B=B, HQ=HQ, HKV=HKV, N_PAGES=N_PAGES), cases, ops, references


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="*", default=["mistral", "smallthinker"],
                    choices=["mistral", "smallthinker"])
    ap.add_argument("--parent", help="a checkout whose kernel is timed in turn (mistral)")
    ap.add_argument("--sizes", nargs="*", default=[], metavar="CHUNKxUPDATE")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default="chiprun_out/ragged_micro.json")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        print("ragged_micro: no TPU; the interpreter's times mean nothing", file=sys.stderr)
        return 1
    from modal_examples_tpu.ops import paged_attention as pa

    rows = []
    for shape in args.shapes:
        dims, cases, ops, reference = globals()[shape](args, jax, jnp, np, pa)
        B, HQ, HKV, N_PAGES = dims["B"], dims["HQ"], dims["HKV"], dims["N_PAGES"]
        keys = jax.random.split(jax.random.PRNGKey(0), 5)
        q = jax.random.normal(keys[0], (B, HQ, D), jnp.bfloat16)
        pages = [
            jax.random.normal(k, (LAYERS, N_PAGES, PAGE, HKV, D), jnp.bfloat16) for k in keys[1:3]
        ]
        new = [jax.random.normal(k, (B, HKV, D), jnp.bfloat16) for k in keys[3:5]]
        for batch, case in cases.items():
            # the arrays are arguments: a closure would compile them in as constants
            operands = (q, *pages, case["tables"], jnp.asarray(case["lens"]), *new)
            ref = reference[batch] if isinstance(reference, dict) else reference
            want = jax.jit(lambda q, kp, vp, pt, lens, kn, vn, ref=ref: ref(
                q, kp, vp, jnp.int32(1), pt, lens, kn, vn
            ))(*operands).astype(jnp.float32)
            live_bytes = case["live"] * HKV * D * 2 * 2
            for name, op in (ops[batch] if batch in ops else ops).items():
                one = jax.jit(lambda q, kp, vp, pt, lens, kn, vn, op=op: op(
                    q, kp, vp, jnp.int32(1), pt, lens, kn, vn
                ))

                def step(q, kp, vp, pt, lens, kn, vn, op=op):
                    def layer(i, x):  # each call waits for the one before it
                        o = op(x, kp, vp, (i % LAYERS).astype(jnp.int32), pt, lens, kn, vn)
                        return (q + o * jnp.bfloat16(1e-3)).astype(q.dtype)

                    return jax.lax.fori_loop(0, CALLS, layer, q)

                step = jax.jit(step)
                err = None
                if UNCHECKED not in name:
                    err = float(jnp.max(jnp.abs(one(*operands).astype(jnp.float32) - want)))
                step(*operands).block_until_ready()  # compiles
                us = []
                for _ in range(3):
                    t0 = time.perf_counter()
                    for _ in range(args.reps):
                        out = step(*operands)
                    out.block_until_ready()
                    us.append((time.perf_counter() - t0) / args.reps / CALLS * 1e6)
                row = dict(
                    shape=shape, batch=batch, op=name, us_call=round(min(us), 2),
                    gb_s_live=round(live_bytes / min(us) / 1e3, 1), max_diff_vs_reference=err,
                    **case.get("note", {}),
                )
                rows.append(row)
                print(json.dumps(row), flush=True)
    out = ROOT / args.out
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps({"device": jax.devices()[0].device_kind, "rows": rows}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
