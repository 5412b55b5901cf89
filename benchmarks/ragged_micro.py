#!/usr/bin/env python3
"""The decode step's attention alone, at Mistral-7B's cache geometry.

    chiprun -- python benchmarks/ragged_micro.py [--parent <checkout>] [--sizes 8x8 16x16 ...]

us a call (one layer's attention of one decode step: 16 slots, 32 heads over
8 KV heads of 128, pages of 16, bf16, a 256-page table a slot over a cache of
3072 scattered pages) and GB/s over the live K/V bytes, for the two ways
``llama.paged_impl_plan`` chooses between: the chunked XLA loop
(``paged_decode_attention_chunked``) and the ragged kernel
(``paged_decode_attention_ragged``) in both variants, at the sizes the kernel
chooses and at each ``--sizes`` CHUNKxUPDATE (pages a half of the DMA ring x
pages a softmax update). Batches: two drawn as ``reason-closed`` draws its
contexts (a prompt of 65-256 and a uniform share of an answer of 512-1024),
and a docqa batch of 12 x ~2600 with 4 dead slots. A call is timed inside a
loop of 32 (a step's layers) so that no dispatch is in it. With ``--parent``
the same through that checkout's kernel. Every output is compared with
``paged_decode_attention_inflight`` over the gathered pages. Needs the chip:
a time from the interpreter says nothing.
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

B, HQ, HKV, D, PAGE, TABLE, LAYERS, N_PAGES, CALLS = 16, 32, 8, 128, 16, 256, 2, 3072, 32


def batches(rng):
    import numpy as np

    def reason():
        prompt, answer = rng.integers(65, 257, B), rng.integers(512, 1025, B)
        return (prompt + rng.random(B) * answer).astype(np.int32)

    docqa = np.array([2600 + 40 * i for i in range(12)] + [0] * 4, np.int32)
    return {"reason_a": reason(), "reason_b": reason(), "docqa_12_of_16": docqa}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", help="a checkout whose kernel is timed in turn")
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

    ops = {"loop": pa.paged_decode_attention_chunked}
    for variant in ("flat", "grouped"):
        sizes = [pa.ragged_kernel_sizes(variant, PAGE, HKV, D, 2, TABLE)]
        sizes += [tuple(int(n) for n in s.split("x")) for s in args.sizes]
        for i, (chunk, update) in enumerate(sizes):
            ops[f"{variant}_{chunk}x{update}" + ("_chosen" if i == 0 else "")] = (
                lambda *a, v=variant, c=chunk, u=update: pa.paged_decode_attention_ragged(
                    *a, variant=v, chunk_pages=c, update_pages=u
                )
            )
    if args.parent:
        spec = importlib.util.spec_from_file_location(
            "modal_examples_tpu.ops._parent_paged",
            Path(args.parent) / "modal_examples_tpu/ops/paged_attention.py",
        )
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
        ops["parent_kernel"] = parent.paged_decode_attention_ragged

    rng = np.random.default_rng(35)
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(keys[0], (B, HQ, D), jnp.bfloat16)
    pages = [
        jax.random.normal(k, (LAYERS, N_PAGES, PAGE, HKV, D), jnp.bfloat16)
        for k in keys[1:3]
    ]
    new = [jax.random.normal(k, (B, HKV, D), jnp.bfloat16) for k in keys[3:5]]
    # every slot its own pages, scattered as an allocator leaves them
    live = 190  # pages a slot: the longest docqa context is 3040 positions
    tables = jnp.pad(
        jnp.asarray(rng.permutation(N_PAGES - 1)[: B * live].reshape(B, live) + 1, jnp.int32),
        ((0, 0), (0, TABLE - live)),
    )
    # the arrays are arguments: a closure would compile them in as constants
    reference = jax.jit(lambda q, kp, vp, pt, lens, kn, vn: pa.paged_decode_attention_inflight(
        q, kp[1][pt], vp[1][pt], lens, kn, vn
    ))
    cases = {}  # batch -> (operands, what the gathered form gives, live K/V bytes)
    for batch, lens in batches(np.random.default_rng(35)).items():
        operands = (q, *pages, tables, jnp.asarray(lens), *new)
        cases[batch] = (
            operands, reference(*operands).astype(jnp.float32),
            int(lens.sum()) * HKV * D * 2 * 2,
        )
    rows = []
    for name, op in ops.items():
        one = jax.jit(lambda q, kp, vp, pt, lens, kn, vn, op=op: op(
            q, kp, vp, jnp.int32(1), pt, lens, kn, vn
        ))

        def step(q, kp, vp, pt, lens, kn, vn, op=op):
            def layer(i, x):  # each call waits for the one before it
                o = op(x, kp, vp, (i % LAYERS).astype(jnp.int32), pt, lens, kn, vn)
                return (q + o * jnp.bfloat16(1e-3)).astype(q.dtype)

            return jax.lax.fori_loop(0, CALLS, layer, q)

        step = jax.jit(step)
        for batch, (operands, want, live_bytes) in cases.items():
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
                batch=batch, op=name, us_call=round(min(us), 2),
                gb_s_live=round(live_bytes / min(us) / 1e3, 1), max_diff_vs_inflight=err,
            )
            rows.append(row)
            print(json.dumps(row), flush=True)
    out = ROOT / args.out
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps({"device": jax.devices()[0].device_kind, "rows": rows}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
