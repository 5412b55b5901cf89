#!/usr/bin/env python3
"""One routed layer of a decode step alone, at a benchmark cell's shapes.

    chiprun -- python benchmarks/expert_swiglu_micro.py [--shape lfm2 mixtral] [--tiles 16 64] [--blocks 512 768]

us a layer for the forms ``moe.expert_scan_form`` chooses between: the XLA
``fori_loop`` of a trip a tile (at the tile before PR 40, every token in one
tile an expert, and at ``expert_tile``'s) and the grouped matmul
(``ops.expert_swiglu``) at the tile and F block it chooses and at each of
``--tiles`` x ``--blocks``; ``layout`` is the kernel's form with the kernel
taken out (the sort, the row gather and the combine alone). A call is
``moe_swiglu_sparse`` on the whole ``[L, E, D, F]`` int8 stacks inside a
``lax.scan`` over the layers, so that no dispatch is in it. ``lfm2``: 64
tokens (62 live), 4 of 64 experts of 2048 x 1536, 16 layers, each expert's
load drawn as the cell's selection bias spreads it (``exp(0.34 N(0, 1))``);
``mixtral``: 16 tokens, 2 of 8 experts of 4096 x 14336, 7 layers, even load.
``floor_us`` is the reached experts' bytes at 819 GB/s. Every form's output
is compared with the first's. Needs the chip: a time from the interpreter
says nothing.
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

#: tokens, live tokens, experts a token, layers, experts, d_model, d_ff, spread of log load
SHAPES = {
    "lfm2": (64, 62, 4, 16, 64, 2048, 1536, 0.34),
    "mixtral": (16, 16, 2, 7, 8, 4096, 14336, 0.0),
}
HBM_GB_S = 819.0


def run_shape(name: str, tiles, blocks, reps: int) -> list[dict]:
    import jax
    import jax.numpy as jnp

    from modal_examples_tpu.models import moe
    from modal_examples_tpu.models.quantize import QuantizedWeight
    from modal_examples_tpu.ops import expert_swiglu as op

    T, live, k, L, E, D, F, spread = SHAPES[name]
    keys = jax.random.split(jax.random.PRNGKey(40), 8)

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def leaf(key, din, dout):  # an expert at a time, made where it lives: no host copy
        def one(key):
            return jax.random.randint(key, (din, dout), -127, 128, jnp.int8)

        q = jax.lax.map(one, jax.random.split(key, L * E)).reshape(L, E, din, dout)
        return QuantizedWeight(q=q, scale=jnp.full((L, E, 1, dout), din**-0.5 / 73.0, jnp.float32))

    weights = (leaf(keys[0], D, F), leaf(keys[1], D, F), leaf(keys[2], F, D))
    x = jax.random.normal(keys[3], (T, D), jnp.float32).astype(jnp.bfloat16)
    # a layer's route: each expert's load exp(spread N(0, 1)), a token's k distinct experts
    load = spread * jax.random.normal(keys[4], (L, 1, E))
    ids = jax.lax.top_k(load + jax.random.gumbel(keys[5], (L, T, E)), k)[1].astype(jnp.int32)
    w = jax.nn.softmax(jax.random.normal(keys[6], (L, T, k)), axis=-1)
    mask = jnp.arange(T) < live
    hit = jax.nn.one_hot(jnp.where(mask[None, :, None], ids, E), E + 1)[..., :E]  # [L, T, k, E]
    per_expert = jnp.sum(hit, axis=(1, 2))  # [L, E]
    reached = float(jnp.mean(jnp.sum(per_expert > 0, axis=1)))
    expert_bytes = 3 * D * F
    row = {
        "shape": name, "experts_reached_mean": reached,
        "pairs_an_expert_max": int(jnp.max(per_expert)),
        "floor_us": round(reached * expert_bytes / HBM_GB_S / 1e3, 1),
    }
    print(json.dumps(row), flush=True)
    rows = [row]

    def layers(scan, tile, kernel=None):
        def run(weights, x, ids, w):
            def layer(acc, scanned):
                i, ids, w = scanned
                out, _ = moe.moe_swiglu_sparse(
                    *weights, x, ids, w, token_mask=mask, layer=i, tile=tile, scan=scan)
                return acc + out, None

            return jax.lax.scan(layer, jnp.zeros((T, D), jnp.float32), (jnp.arange(L), ids, w))[0]

        def patched(*args):  # the kernel's stand-in is read while tracing
            if kernel is None:
                return run(*args)
            keep, moe.expert_swiglu = moe.expert_swiglu, kernel
            try:
                return run(*args)
            finally:
                moe.expert_swiglu = keep

        return jax.jit(patched)

    def layout_only(gate, up, down, rows, *scalars, **kw):
        return jnp.zeros(rows.shape, jnp.float32)

    old_tile, new_tile = min(128, -(-T // 16) * 16), moe.expert_tile(T, k, E)
    forms = {f"xla_tile{old_tile}": layers("xla", old_tile)}
    if new_tile != old_tile:
        forms[f"xla_tile{new_tile}"] = layers("xla", new_tile)
    forms[f"kernel_tile{new_tile}_chosen"] = layers("pallas", new_tile)
    forms[f"layout_tile{new_tile}"] = layers("pallas", new_tile, layout_only)
    for tile in tiles:
        for bf in blocks or [None]:
            kernel = op.expert_swiglu if bf is None else functools.partial(op.expert_swiglu, block_f=bf)
            forms[f"kernel_tile{tile}_f{bf or 'chosen'}"] = layers("pallas", tile, kernel)

    want = None
    for form, fn in forms.items():
        row = {"shape": name, "form": form}
        try:
            got = fn(weights, x, ids, w)
            got.block_until_ready()
            if want is None:
                want = got
            if not form.startswith("layout"):
                row["max_diff"] = float(jnp.max(jnp.abs(got - want)))
                row["max_abs"] = float(jnp.max(jnp.abs(want)))
            us = []
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(reps):
                    got = fn(weights, x, ids, w)
                got.block_until_ready()
                us.append((time.perf_counter() - t0) / reps / L * 1e6)
            row.update(us_layer=round(min(us), 1), us_an_expert=round(min(us) / reached, 2))
        except Exception as e:  # a block Mosaic refuses is a row, not the end of the sweep
            row["error"] = f"{type(e).__name__}: {str(e)[-400:]}"
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", nargs="*", default=["lfm2"], choices=sorted(SHAPES))
    ap.add_argument("--tiles", nargs="*", type=int, default=[])
    ap.add_argument("--blocks", nargs="*", type=int, default=[])
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default="chiprun_out/expert_swiglu_micro.json")
    args = ap.parse_args()

    import jax

    if jax.default_backend() != "tpu":
        print("expert_swiglu_micro: no TPU; the interpreter's times mean nothing", file=sys.stderr)
        return 1
    rows = []
    for name in args.shape:
        rows += run_shape(name, args.tiles, args.blocks, args.reps)
    out = ROOT / args.out
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps({"device": jax.devices()[0].device_kind, "rows": rows}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
