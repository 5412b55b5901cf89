#!/usr/bin/env python3
"""Granite-4.0-H's decode state step alone, at the benchmark cell's shapes.

    chiprun -- python benchmarks/ssm_step_micro.py [--tiles 1x8 1x32 2x64 ...] [--groups 1]

us a layer and GB/s over ``2 x 134 MB`` (a layer's ``[64, 64, 64, 128]``
float32 state read and written once) for the two forms
``granite_hybrid.paged_impl_plan`` chooses between: XLA's pair of fusions
(``ssm_step_xla``: the in-place update, then the reduction that reads the
state again) and the kernel (``ssm_step``) at the tile it chooses and at each
``--tiles`` SLOTSxHEADS. A call is one layer of the whole ``[36, 64, 64, 64,
128]`` leaf inside a ``lax.scan`` of 9 (a Mamba segment), the leaf donated,
so that no dispatch and no copy is in it; 62 of the 64 slots are live. Every
form's ``y`` and state are compared with the XLA form's on a 9-layer leaf.
Needs the chip: a time from the interpreter says nothing.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

LAYERS, S, H, P, N, SEGMENT = 36, 64, 64, 64, 128, 9
LAYER_BYTES = S * H * P * N * 4


def build(forms: dict, groups: int, reps: int):
    """Time each of ``forms`` (name -> ``f(ssm, layer, decay, dtx, B, C)``)
    and compare it with the first; returns the rows."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=0)
    def leaf(layers):  # [layers, S, H, P, N], made where it lives: no host copy
        at = [jax.lax.broadcasted_iota(jnp.float32, (layers, S, H, P, N), d) for d in range(5)]
        return 0.1 * jnp.sin(at[0] * 1.3 + at[1] * 0.7 + at[2] * 0.11 + at[3] * 0.013 + at[4] * 0.0017)

    keys = jax.random.split(jax.random.PRNGKey(37), 4)
    live = (jnp.arange(S) % 32 != 7)[None, :]  # 62 of 64, as the cell's decode_batch_mean
    decay = jnp.where(live[..., None], jax.random.uniform(keys[0], (SEGMENT, S, H), jnp.float32, 0.6, 1.0), 1.0)
    dtx = jnp.where(live[..., None, None], jax.random.normal(keys[1], (SEGMENT, S, H, P), jnp.float32), 0.0)
    B, C = (jax.random.normal(k, (SEGMENT, S, groups, N), jnp.float32) for k in keys[2:])
    small = (decay, dtx, B, C)

    def segment(form):
        def run(ssm, first, decay, dtx, B, C):
            def layer(ssm, scanned):
                i, *row = scanned
                return form(ssm, i, *row)

            return jax.lax.scan(layer, ssm, (first + jnp.arange(SEGMENT), decay, dtx, B, C))

        return jax.jit(run, donate_argnums=0)

    rows, want = [], None
    for name, form in forms.items():
        run = segment(form)
        row = {"form": name}
        try:
            ssm, y = run(leaf(SEGMENT), jnp.int32(0), *small)
            got = (ssm, y)
            if want is None:
                want = got
            row["max_diff_state"] = float(jnp.max(jnp.abs(got[0] - want[0])))
            row["max_diff_y"] = float(jnp.max(jnp.abs(got[1] - want[1])))
            row["max_abs_y"] = float(jnp.max(jnp.abs(got[1])))
            del ssm, y, got
            ssm = leaf(LAYERS)
            ssm, y = run(ssm, jnp.int32(LAYERS - SEGMENT), *small)  # compiles for the whole leaf
            y.block_until_ready()
            us = []
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(reps):
                    ssm, y = run(ssm, jnp.int32(LAYERS - SEGMENT), *small)
                y.block_until_ready()
                us.append((time.perf_counter() - t0) / reps / SEGMENT * 1e6)
            del ssm, y
            row.update(us_layer=round(min(us), 1), gb_s=round(2 * LAYER_BYTES / min(us) / 1e3, 1))
        except Exception as e:  # a tile Mosaic refuses is a row, not the end of the sweep
            row["error"] = f"{type(e).__name__}: {str(e)[-300:]}"
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiles", nargs="*", default=[], metavar="SLOTSxHEADS")
    ap.add_argument("--groups", type=int, default=1)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default="chiprun_out/ssm_step_micro.json")
    args = ap.parse_args()

    import jax

    if jax.default_backend() != "tpu":
        print("ssm_step_micro: no TPU; the interpreter's times mean nothing", file=sys.stderr)
        return 1
    from modal_examples_tpu.ops import ssm_step as op

    chosen = op.ssm_step_tile(S, H, P, N)
    forms = {"xla": op.ssm_step_xla, "kernel_%dx%d_chosen" % chosen: op.ssm_step}
    for text in args.tiles:
        tile = tuple(int(n) for n in text.split("x"))
        forms["kernel_%dx%d" % tile] = lambda *a, tile=tile: op.ssm_step(*a, tile=tile)
    rows = build(forms, args.groups, args.reps)
    out = ROOT / args.out
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps({"device": jax.devices()[0].device_kind, "rows": rows}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
