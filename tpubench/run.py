#!/usr/bin/env python3
"""Run one cell of the benchmark that ``BENCHMARK.json`` defines.

    python3 tpubench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1> [--rehearse]

From the root of a checkout, on a machine with the chips the cell asks
for. The cell's deployment is built from the seed, warmed up on its own
shapes (set-up), then driven for ``--seconds`` by its traffic mix. The
last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``,
with ``--trace 1`` a ``breakdown``, and last the numbers compared with the
plain reference beside their limits (``checks``), which also end
standard error.

Without an accelerator, or with fewer chips than the cell asks for, it
exits 3 and prints no result. ``--rehearse`` runs the cell at its tiny
rehearsal sizes and accepts the CPU (``JAX_PLATFORMS=cpu``).
"""
import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes; accepts the CPU")
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(1, os.path.join(ROOT, "src"))
    from harness import runner
    try:
        result = runner.run(args.workload, args.seed, args.seconds,
                            bool(args.trace), rehearse=args.rehearse,
                            root=ROOT, t_start=T_START)
    except runner.NoChip as e:
        print(f"tpubench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
