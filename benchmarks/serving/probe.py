#!/usr/bin/env python3
"""One run of a cell with what the command does not offer: a paced rate other
than the cell's (the knee sweep), the int4 control beside the comparison with
the reference, the program's own lower-precision path as a control (``--env
MTPU_KV_DTYPE=int8``: the engine with an int8 KV cache), and a dump of every
request and the reduced trace.

    python3 benchmarks/serving/probe.py --workload <cell> --seed <n> --seconds <s>
        [--trace 1] [--rate <rps>] [--control] [--env NAME=VALUE]... [--dump <file>]

The benchmark's own runs never come through here.
"""

from __future__ import annotations

import argparse
import json

import run


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rate", type=float)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--env", action="append", default=[], metavar="NAME=VALUE")
    ap.add_argument("--dump")
    args = ap.parse_args()
    result = run.run_cell(
        args.workload, args.seed, args.seconds, bool(args.trace),
        control=args.control, dump=args.dump,
        extra_env=dict(pair.split("=", 1) for pair in args.env),
        mix_overrides={"rate_rps": args.rate} if args.rate else None,
    )
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
