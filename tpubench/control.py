#!/usr/bin/env python3
"""The control of a cell's comparison: the plain reference, computed a
step of precision lower (bfloat16), put in the program's place.

    python3 tpubench/control.py --workload <cell> --units <n> \
        --seeds <s> [<s> ...] [--rehearse]

For each seed it builds, through the cell's kind, the inputs that a run
of the cell with ``--units`` chunks, batches or requests in its window
compares (the answers sampled from the seed), runs the reference at the
cell's precision and at bfloat16, and prints the numbers that decide
``correct`` beside the cell's limits. A sound comparison reads the control as not correct
on every seed. The benchmark's runs never run it.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def readings(workload, seeds, units, *, rehearse=False, root=ROOT):
    """{seed: {number: control reading}} and the cell's limits."""
    from harness import registry
    reg = registry.Registry(root)
    cell = reg.cell(workload)
    cfg, tr = reg.config(cell["config"]), reg.traffic(cell["traffic"])
    if rehearse:
        cfg, tr = registry.rehearsal(cfg), registry.rehearsal(tr)
    kind = reg.kind(tr["kind"])
    out = {}
    for seed in seeds:
        inp = kind.inputs(cfg, tr, seed, units)
        ref = kind.reference(inp, kind.PRECISION)
        ctl = kind.reference(inp, "bfloat16")
        out[seed] = kind.gaps(ref, ctl, inp)
    return out, reg.limits(workload)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--units", type=int, required=True,
                    help="chunks, batches or requests of a run's window")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(1, os.path.join(ROOT, "src"))
    out, limits = readings(args.workload, args.seeds, args.units,
                           rehearse=args.rehearse)
    failed_all = True
    for seed, nums in out.items():
        fails = [k for k, v in nums.items() if v > limits[k]]
        failed_all &= bool(fails)
        print(f"seed {seed}: " + ", ".join(
            f"{k} {v!r} (limit {limits[k]!r})" for k, v in nums.items())
            + f" -> {'not correct' if fails else 'CORRECT'}",
            file=sys.stderr)
    print(json.dumps({"workload": args.workload, "units": args.units,
                      "readings": {str(s): v for s, v in out.items()},
                      "limits": limits, "control_fails_every_seed":
                      failed_all}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
