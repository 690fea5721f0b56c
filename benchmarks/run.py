# One function per paper table. Print ``name,us_per_call,derived`` CSV.
# ``--json`` additionally writes one BENCH_<module>.json trajectory file per
# module (deterministic: sorted keys, rows in emission order) under
# ``--out-dir`` so bench artifacts don't land in the repo root.
# ``--check`` compares the fresh rows against the committed repo-root
# snapshots with a tolerance band and fails the run on planner-throughput
# regressions, writing the full diff as a BENCH_diff.json artifact.
import argparse
import json
import os
import platform
import re
import sys
import time
import traceback

# the bench trajectory was previously unguarded: rows guarded here fail
# the run when a fresh measurement is slower than the committed snapshot
# by more than the tolerance band (same-machine comparison; CI runners
# are noisy, hence the generous band and the restriction to the
# largest-size rows — small-M rows jitter well past any sane band)
GUARD_PREFIXES = ("planner.", "online.")
GUARD_SUFFIXES = (".M64000", ".R256")
CHECK_TOLERANCE = 0.30

# fleet-mesh scaling rows (``<base>.sharded_dN`` / ``<base>.ref1``) are
# guarded against their SAME-RUN single-device reference, never the
# committed snapshot: forced CPU meshes only parallelize up to the
# machine's real core count, so the floor is calibrated to it — the
# acceptance 2x on a >=4-effective-core mesh, a soft fraction of the
# effective parallelism below that (a 1-core box can't speed up at all;
# the guard then only catches sharding that *destroys* throughput)
_SHARDED_RE = re.compile(r"^(?P<base>.+)\.sharded_d(?P<d>\d+)$")
SHARD_FLOOR_FULL = 2.0

# chunk-boundary checkpointing ceiling: each ``engine_step_ckpt_*`` row
# is paired with its SAME-RUN ``engine_step_ckptoff_*`` twin (identical
# fleet, chunks, interleaved rounds — the delta is the snapshot + async
# npy handoff alone, tail wait included) and must stay within 10% of it
_CKPT_RE = re.compile(r"^streams\.engine_step_ckpt_(?P<size>.+)$")
CKPT_TOLERANCE = 0.10

# engine-backend memory floor: each ``<base>.logmem`` row is paired with
# its SAME-RUN ``<base>.exact`` row by the ``bytes_per_stream`` extras —
# device bytes are deterministic, so the floor has no tolerance band.
# The O(log K) backend must stay >= 8x leaner than the O(K) reservoir at
# K >= 4096 (at small K the fixed O(log K) footprint eats the margin)
_BACKEND_RE = re.compile(r"^(?P<base>.+)\.(?P<backend>exact|logmem)$")
MEMORY_FLOOR_FULL_K = 4096
MEMORY_FLOOR_FULL = 8.0
MEMORY_FLOOR_SMALL = 4.0


def memory_ratio_floor(k: int) -> float:
    return (MEMORY_FLOOR_FULL if k >= MEMORY_FLOOR_FULL_K
            else MEMORY_FLOOR_SMALL)


def shard_speedup_floor(devices: int) -> float:
    eff = min(devices, os.cpu_count() or 1)
    return SHARD_FLOOR_FULL if eff >= 4 else 0.45 * eff


def _guarded(name: str) -> bool:
    return (name.startswith(GUARD_PREFIXES)
            and name.endswith(GUARD_SUFFIXES))


def host_meta() -> dict:
    """The measurement context stamped into every trajectory file: which
    machine and numeric regime produced the numbers (cross-machine
    comparisons lean on ``_numpy_oracle`` calibration, but the metadata
    makes the provenance inspectable)."""
    meta = {"platform": platform.platform(),
            "cpu_count": os.cpu_count(),
            "python": platform.python_version()}
    try:
        import jax
        meta["jax_version"] = jax.__version__
        meta["jax_backend"] = jax.default_backend()
        meta["jax_x64"] = bool(jax.config.jax_enable_x64)
    except Exception:  # pragma: no cover - jax is a hard dep in practice
        meta["jax_version"] = None
    return meta


def write_trajectory(name: str, rows: list, path: str | None = None,
                     out_dir: str | None = None) -> str:
    """Write one BENCH_<name>.json trajectory file (the uniform format all
    bench entry points share): sorted keys, rows in emission order, plus
    the host-metadata block."""
    if path is None:
        d = out_dir or "."
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump({"bench": name, "host": host_meta(), "rows": rows},
                  f, indent=1, sort_keys=True)
    return path


def check_regressions(fresh: dict, baseline_dir: str = ".",
                      tol: float = CHECK_TOLERANCE,
                      out_dir: str | None = None) -> list:
    """Compare fresh rows ({module: rows}) against the committed
    ``BENCH_<module>.json`` snapshots.

    Rows are matched by name; a *guarded* row (``GUARD_PREFIXES``)
    regresses when ``fresh_us > committed_us * (1 + tol)``. Unmatched or
    unguarded rows are reported informationally only. Writes the full
    comparison to ``BENCH_diff.json`` under ``out_dir`` (the CI
    artifact) and returns the list of regression dicts."""
    diff, regressions = [], []
    for module, rows in fresh.items():
        base_path = os.path.join(baseline_dir, f"BENCH_{module}.json")
        committed = {}
        if os.path.exists(base_path):
            with open(base_path) as f:
                committed = {r["name"]: r for r in json.load(f)["rows"]}
        # cross-machine calibration: the committed snapshot was produced
        # on some machine; the `_numpy_oracle` reference rows measure the
        # same unchanged host code on both, so their ratio estimates the
        # machine-speed delta and rescales the comparison
        scales = [row["us_per_call"] / committed[row["name"]]["us_per_call"]
                  for row in rows
                  if "_numpy_oracle" in row["name"]
                  and row["name"] in committed
                  and committed[row["name"]]["us_per_call"]]
        scale = sorted(scales)[len(scales) // 2] if scales else 1.0
        for row in rows:
            name = row["name"]
            entry = {"name": name, "us_new": row["us_per_call"],
                     "guarded": _guarded(name), "machine_scale": scale}
            old = committed.get(name)
            if old is None:
                entry["status"] = "new"
            else:
                entry["us_committed"] = old["us_per_call"]
                ratio = (row["us_per_call"]
                         / (old["us_per_call"] * scale)
                         if old["us_per_call"] else float("inf"))
                entry["ratio"] = ratio
                slow = ratio > 1.0 + tol
                entry["status"] = ("regression" if slow and entry["guarded"]
                                   else "slower" if slow else "ok")
                if entry["status"] == "regression":
                    regressions.append(entry)
            diff.append(entry)
        # a guarded committed row that no fresh row matches means the
        # guard was silently defeated (renamed emit label, changed size
        # constant, dropped row) — fail loudly instead of passing green
        fresh_names = {row["name"] for row in rows}
        for name, old in committed.items():
            if _guarded(name) and name not in fresh_names:
                entry = {"name": name, "us_committed": old["us_per_call"],
                         "guarded": True, "status": "missing"}
                regressions.append(entry)
                diff.append(entry)
        # fleet-mesh rows: same-run pairing against the .ref1 reference
        by_name = {row["name"]: row for row in rows}
        for row in rows:
            match = _SHARDED_RE.match(row["name"])
            if match is None:
                continue
            devices = int(match.group("d"))
            floor = shard_speedup_floor(devices)
            entry = {"name": row["name"], "us_new": row["us_per_call"],
                     "guarded": True, "floor": floor,
                     "effective_cores": min(devices, os.cpu_count() or 1)}
            ref = by_name.get(match.group("base") + ".ref1")
            if ref is None or not row["us_per_call"]:
                entry["status"] = "missing_ref"
                regressions.append(entry)
            else:
                speedup = ref["us_per_call"] / row["us_per_call"]
                entry["us_ref1"] = ref["us_per_call"]
                entry["speedup"] = speedup
                entry["status"] = ("sharded_slow" if speedup < floor
                                   else "ok")
                if entry["status"] == "sharded_slow":
                    regressions.append(entry)
            diff.append(entry)
        # checkpointing rows: same-run pairing against the no-checkpoint
        # twin — the chunk-boundary snapshot + async write handoff must
        # stay within CKPT_TOLERANCE of the bare ingest loop
        for row in rows:
            match = _CKPT_RE.match(row["name"])
            if match is None:
                continue
            entry = {"name": row["name"], "us_new": row["us_per_call"],
                     "guarded": True, "tol": CKPT_TOLERANCE}
            ref = by_name.get(
                f"streams.engine_step_ckptoff_{match.group('size')}")
            if ref is None or not ref["us_per_call"]:
                entry["status"] = "missing_ckptoff_ref"
                regressions.append(entry)
            else:
                overhead = row["us_per_call"] / ref["us_per_call"] - 1.0
                entry["us_ckptoff"] = ref["us_per_call"]
                entry["overhead"] = overhead
                entry["status"] = ("ckpt_slow"
                                   if overhead > CKPT_TOLERANCE
                                   else "ok")
                if entry["status"] == "ckpt_slow":
                    regressions.append(entry)
            diff.append(entry)
        # engine-backend rows: same-run memory pairing — a logmem row
        # whose exact twin is missing (or whose bytes advantage drops
        # under the floor) fails the run
        for row in rows:
            match = _BACKEND_RE.match(row["name"])
            if match is None or match.group("backend") != "logmem" \
                    or "bytes_per_stream" not in row:
                continue
            k = int(row.get("k", 0))
            floor = memory_ratio_floor(k)
            entry = {"name": row["name"], "guarded": True, "k": k,
                     "floor": floor,
                     "bytes_logmem": row["bytes_per_stream"]}
            ref = by_name.get(match.group("base") + ".exact")
            if (ref is None or "bytes_per_stream" not in ref
                    or not row["bytes_per_stream"]):
                entry["status"] = "missing_pair"
                regressions.append(entry)
            else:
                ratio = ref["bytes_per_stream"] / row["bytes_per_stream"]
                entry["bytes_exact"] = ref["bytes_per_stream"]
                entry["bytes_ratio"] = ratio
                entry["status"] = ("logmem_memory" if ratio < floor
                                   else "ok")
                if entry["status"] == "logmem_memory":
                    regressions.append(entry)
            diff.append(entry)
    path = write_trajectory("diff", diff, out_dir=out_dir)
    print(f"wrote {path} ({len(regressions)} guarded regression(s), "
          f"tolerance {tol:.0%})")
    for entry in regressions:
        if entry["status"] == "missing":
            print(f"  MISSING guarded row {entry['name']} "
                  f"(committed {entry['us_committed']:.1f}us)")
        elif entry["status"] == "missing_ref":
            print(f"  MISSING same-run .ref1 reference for "
                  f"{entry['name']}")
        elif entry["status"] == "sharded_slow":
            print(f"  SHARDED-SLOW {entry['name']}: "
                  f"{entry['speedup']:.2f}x vs same-run ref, floor "
                  f"{entry['floor']:.2f}x "
                  f"({entry['effective_cores']} effective core(s))")
        elif entry["status"] == "missing_ckptoff_ref":
            print(f"  MISSING same-run engine_step_ckptoff twin for "
                  f"{entry['name']}")
        elif entry["status"] == "ckpt_slow":
            print(f"  CKPT-SLOW {entry['name']}: "
                  f"{entry['overhead']:+.1%} over the same-run "
                  f"no-checkpoint twin ({entry['us_new']:.1f}us vs "
                  f"{entry['us_ckptoff']:.1f}us), ceiling "
                  f"{entry['tol']:.0%}")
        elif entry["status"] == "missing_pair":
            print(f"  MISSING same-run .exact memory pair for "
                  f"{entry['name']}")
        elif entry["status"] == "logmem_memory":
            print(f"  LOGMEM-MEMORY {entry['name']}: only "
                  f"{entry['bytes_ratio']:.1f}x leaner than exact "
                  f"({entry['bytes_logmem']:.0f} vs "
                  f"{entry['bytes_exact']:.0f} B/stream), floor "
                  f"{entry['floor']:.1f}x at K={entry['k']}")
        else:
            print(f"  REGRESSION {entry['name']}: "
                  f"{entry['us_committed']:.1f}us -> "
                  f"{entry['us_new']:.1f}us ({entry['ratio']:.2f}x)")
    return regressions


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run a single module (tables|curves|fig8|writes|"
                         "kernels|roofline|streams|planner)")
    ap.add_argument("--json", action="store_true",
                    help="also write BENCH_<module>.json per module")
    ap.add_argument("--out-dir", default="bench_out",
                    help="directory for BENCH_*.json artifacts "
                         "(default: bench_out)")
    ap.add_argument("--check", action="store_true",
                    help="compare fresh rows against the committed "
                         "BENCH_*.json snapshots; exit 1 on guarded "
                         "(planner/online) regressions beyond the band")
    ap.add_argument("--check-tol", type=float, default=CHECK_TOLERANCE,
                    help="relative slowdown tolerated by --check "
                         "(default: 0.30)")
    ap.add_argument("--baseline-dir", default=".",
                    help="directory holding the committed snapshots "
                         "(default: repo root)")
    args = ap.parse_args()
    from repro.core import jaxcompat
    jaxcompat.compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks import (algo_writes, fig8_trace, fig_curves,
                            kernels_bench, paper_tables, planner_bench,
                            roofline, streams_bench)
    modules = {
        "tables": paper_tables,    # Tables I & II + the 3-tier S3 table
        "curves": fig_curves,      # Figures 4 & 5
        "fig8": fig8_trace,        # Figure 8 trace validation
        "writes": algo_writes,     # eqs. 2-8
        "kernels": kernels_bench,  # Pallas-op microbench
        "roofline": roofline,      # dry-run roofline table
        "streams": streams_bench,  # multi-tenant fleet engine throughput
        "planner": planner_bench,  # closed-form fleet planning throughput
    }
    failures = 0
    fresh = {}
    print("name,us_per_call,derived")
    for name, mod in modules.items():
        if args.only and name != args.only:
            continue
        rows = []

        def emit(row_name: str, us_per_call: float, derived: str = "",
                 **extra) -> None:
            print(f"{row_name},{us_per_call:.1f},{derived}")
            rows.append({"name": row_name, "us_per_call": us_per_call,
                         "derived": derived, **extra, "ts": time.time()})

        try:
            mod.run(emit)
        except Exception as e:
            failures += 1
            emit(f"{name}.FAILED", 0.0, repr(e))
            traceback.print_exc(file=sys.stderr)
        fresh[name] = rows
        if args.json:
            write_trajectory(name, rows, out_dir=args.out_dir)
    regressions = []
    if args.check:
        regressions = check_regressions(fresh, args.baseline_dir,
                                        args.check_tol, args.out_dir)
    if failures or regressions:
        raise SystemExit(1)


if __name__ == '__main__':
    main()
