#!/usr/bin/env python3
"""The prefill flash kernel alone, at the serving cells' own geometries.

    chiprun -- python benchmarks/flash_micro.py [--parent <checkout>] [--tiles 512x512 ...]

ms a call (bf16, causal) of ``ops.flash_attention._flash_forward`` for the
chunk calls of the docqa cells (Mistral / Mixtral: 32 heads over 8, width
128; DeepSeek-V2: 128 heads, q/k 192 over values 128; 2048 query rows at
offset 2048 and 0; the tail chunks of 128-1024 rows over 2048 cached keys)
and the reason cells' 4 x 256 bucket call, with the tiles
the kernel chooses and with each of ``--tiles``. With ``--parent`` the same
calls through that checkout's kernel, before and after, and the largest
difference between the two outputs. Needs the chip: a time from the
interpreter says nothing.
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

#: name -> (q shape, k shape, v shape, q_offset)
SHAPES = {
    "gqa_off2048": ((1, 32, 2048, 128), (1, 8, 4096, 128), (1, 8, 4096, 128), 2048),
    "gqa_off0": ((1, 32, 2048, 128), (1, 8, 2048, 128), (1, 8, 2048, 128), 0),
    "mla_off2048": ((1, 128, 2048, 192), (1, 128, 4096, 192), (1, 128, 4096, 128), 2048),
    "mla_off0": ((1, 128, 2048, 192), (1, 128, 2048, 192), (1, 128, 2048, 128), 0),
    "bucket_4x256": ((4, 32, 256, 128), (4, 8, 256, 128), (4, 8, 256, 128), 0),
}
# the last chunk of a docqa prompt (PR 33): w query rows over 2048 cached keys
# and its own, w the bucket that holds what is left (2048: *_off2048 above)
for _w in (128, 256, 512, 1024):
    SHAPES[f"gqa_tail{_w}"] = (
        (1, 32, _w, 128), (1, 8, 2048 + _w, 128), (1, 8, 2048 + _w, 128), 2048,
    )
    SHAPES[f"mla_tail{_w}"] = (
        (1, 128, _w, 192), (1, 128, 2048 + _w, 192), (1, 128, 2048 + _w, 128), 2048,
    )


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", help="a checkout whose kernel is timed in turn")
    ap.add_argument("--tiles", nargs="*", default=[], metavar="QxK")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--out", default="chiprun_out/flash_micro.json")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        print("flash_micro: no TPU; the interpreter's times mean nothing", file=sys.stderr)
        return 1
    from modal_examples_tpu.ops.flash_attention import _flash_forward, choose_blocks

    parent = None
    if args.parent:
        spec = importlib.util.spec_from_file_location(
            "modal_examples_tpu.ops._parent_flash",
            Path(args.parent) / "modal_examples_tpu/ops/flash_attention.py",
        )
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)

    def timed(fn, operands):
        out = fn(*operands).block_until_ready()  # compiles
        ms = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(args.reps):
                out = fn(*operands)
            out.block_until_ready()
            ms.append(round((time.perf_counter() - t0) / args.reps * 1e3, 4))
        return out, ms

    rows = []

    def report(**row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    tiles = [None] + [tuple(int(n) for n in t.split("x")) for t in args.tiles]
    for name, (qs, ks, vs, off) in SHAPES.items():
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        operands = [
            jax.random.normal(key, shape, jnp.bfloat16)
            for key, shape in zip(keys, (qs, ks, vs))
        ]
        ref = None
        if parent:
            p_fn = jax.jit(
                lambda q, k, v: parent.flash_attention_chunked(q, k, v, q_offset=off)
            )
            ref, ms = timed(p_fn, operands)
            report(shape=name, who="parent", ms=ms)
        for tile in tiles:
            bq, bk = tile or (None, None)
            if tile and (qs[2] % bq or ks[2] % bk):
                continue
            fn = jax.jit(lambda q, k, v: _flash_forward(
                q, k, v, causal=True, sm_scale=qs[-1] ** -0.5, interpret=False,
                block_q=bq, block_k=bk, q_offset=off,
            )[0])
            out, ms = timed(fn, operands)
            row = dict(
                shape=name, who="change", chosen=tile is None, ms=ms,
                tile="%dx%d" % (
                    tile or choose_blocks(qs[2], ks[2], qs[3], vs[3], 2)
                ),
            )
            if ref is not None:
                row["max_diff_vs_parent"] = float(jnp.max(jnp.abs(
                    out.astype(jnp.float32) - ref.astype(jnp.float32)
                )))
            report(**row)
        if parent:
            report(shape=name, who="parent", ms=timed(p_fn, operands)[1])
    out = ROOT / args.out
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps({
        "device": jax.devices()[0].device_kind, "rows": rows,
    }, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
