#!/usr/bin/env python3
"""perfbench/knee.py — the one sweep that finds an open-loop cell's knee.

    python3 perfbench/knee.py --workload <cell> --rates 20,30,40 \
        --seconds 30 --seeds 1 [--gap-cv 2] [--trace 0] \
        [--manifest <BENCHMARK.json>] [--data-dir <dir> ...] [--out <dir>]

It is run by hand once, when an open-loop cell is defined; the benchmark
never runs it.  For each rate and seed it runs perfbench/run.py's `main` in a
fresh process, on a copy of the cell's traffic file with `rate_per_s` (and
`gap_cv`, if given) replaced.  The copy is found by putting a scratch
directory ahead of the others in `run.DATA_DIRS`, as the CPU tests find
their fixtures: run.py has no flag or variable for it.  The child keeps the
window's rows, and this process, which never touches JAX, reads them.

Per run it prints the median and 95th percentile of the wall from the
scheduled arrival, the late share, the backlog ratio (median wall in the
window's last fifth over that in its first, perfbench/stats.py), the mean
wall and service time, and, for one client, the mean wall an M/D/1 queue
would give at the measured service time.  The knee is the highest rate at
which no run had a late request and every backlog ratio stayed under
KNEE_BACKLOG.  The last stdout line is a JSON object of all of it.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
KNEE_BACKLOG = 1.25


def child(spec: dict) -> int:
    """One run, in this process: the rows of its open window go to
    spec["rows"]."""
    from perfbench import run

    run.MANIFEST = spec["manifest"]
    run.DATA_DIRS = spec["data_dirs"] + run.DATA_DIRS
    serve = run.open_window

    def keep(*args, **kwargs):
        out = serve(*args, **kwargs)
        with open(spec["rows"], "w") as f:
            json.dump({k: out[k] for k in ("requests", "window_s",
                                           "arrivals", "client_lag_s")}, f)
        return out

    run.open_window = keep
    return run.main(spec["argv"])


def summary(rows: list, window_s: float, clients: int) -> dict:
    """The numbers of one run's rows (see the module's docstring); a pure
    function."""
    from perfbench import stats

    walls = [r["wall_s"] for r in rows]
    service = [r["service_s"] for r in rows if not r["late"]]
    out = {"late_share": sum(r["late"] for r in rows) / len(rows),
           "p50_ms": stats.percentile(walls, 50) * 1e3,
           "p95_ms": stats.percentile(walls, 95) * 1e3,
           "mean_ms": sum(walls) / len(walls) * 1e3,
           "backlog_ratio": stats.backlog_ratio(rows, window_s),
           "service_ms": None, "rho": None, "md1_ms": None}
    if service:
        s = sum(service) / len(service)
        rho = len(rows) / window_s * s / clients
        out.update(service_ms=s * 1e3, rho=rho)
        if clients == 1 and rho < 1:
            out["md1_ms"] = (s + rho * s / (2 * (1 - rho))) * 1e3
    return out


def knee(runs: list):
    """The highest rate whose every run was correct, had no late request
    and a backlog ratio under KNEE_BACKLOG; None if no rate did."""
    ok = {}
    for r in runs:
        good = (r.get("correct") is True and r.get("late_share") == 0
                and r.get("backlog_ratio") is not None
                and r["backlog_ratio"] < KNEE_BACKLOG)
        ok[r["rate_per_s"]] = ok.get(r["rate_per_s"], True) and good
    passing = [rate for rate, good in ok.items() if good]
    return max(passing) if passing else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--rates", help="requests per second, comma-separated")
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--seeds", default="1", help="comma-separated")
    ap.add_argument("--gap-cv", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--data-dir", action="append", default=[])
    ap.add_argument("--out", default=os.path.join(ROOT, "_scratch", "knee"))
    ap.add_argument("--child", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    if args.child:
        return child(json.loads(args.child))
    if None in (args.workload, args.rates, args.seconds):
        ap.error("--workload, --rates and --seconds are required")

    from perfbench import run

    run.MANIFEST = os.path.abspath(args.manifest)
    data_dirs = [os.path.abspath(d) for d in args.data_dir]
    run.DATA_DIRS = data_dirs + run.DATA_DIRS
    try:
        cell = run.load_cell(run.MANIFEST, args.workload)
    except run.Refused as e:
        print(f"knee: {e}", file=sys.stderr)
        return 2
    params = cell["params"]
    if "arrivals" not in params:
        print(f"knee: {args.workload}'s traffic {cell['traffic']!r} has no "
              f"`arrivals`: a closed loop has no knee", file=sys.stderr)
        return 2
    os.makedirs(args.out, exist_ok=True)
    scratch = tempfile.mkdtemp(prefix="perfbench-knee-")
    runs = []
    try:
        for rate in sorted(float(x) for x in args.rates.split(",")):
            for seed in (int(x) for x in args.seeds.split(",")):
                arr = dict(params["arrivals"], rate_per_s=rate)
                if args.gap_cv is not None:
                    arr["gap_cv"] = args.gap_cv
                os.makedirs(os.path.join(scratch, "workloads"),
                            exist_ok=True)
                with open(os.path.join(scratch, "workloads",
                                       cell["traffic"] + ".json"), "w") as f:
                    json.dump(dict(params, arrivals=arr), f)
                tag = f"{args.workload}-r{rate:g}-cv{arr['gap_cv']:g}-" \
                      f"s{seed}-t{args.trace}"
                rows_path = os.path.join(scratch, tag + ".rows.json")
                spec = {"manifest": run.MANIFEST,
                        "data_dirs": [scratch] + data_dirs,
                        "rows": rows_path,
                        "argv": ["--workload", args.workload,
                                 "--seed", str(seed),
                                 "--seconds", str(args.seconds),
                                 "--trace", str(args.trace)]}
                with open(os.path.join(args.out, tag + ".out"), "w") as out, \
                        open(os.path.join(args.out, tag + ".err"), "w") as err:
                    rc = subprocess.call(
                        [sys.executable, os.path.abspath(__file__),
                         "--child", json.dumps(spec)],
                        stdout=out, stderr=err, cwd=ROOT)
                with open(os.path.join(args.out, tag + ".out")) as f:
                    lines = f.read().strip().splitlines()
                row = {"rate_per_s": rate, "gap_cv": arr["gap_cv"],
                       "clients": arr["clients"], "seed": seed,
                       "trace": args.trace, "rc": rc}
                if rc == 0 and lines:
                    res = json.loads(lines[-1])
                    row.update(correct=res["correct"],
                               attempted=res["attempted"],
                               failed=res["failed"],
                               metrics={k: v["value"] for k, v
                                        in res["metrics"].items()})
                if os.path.exists(rows_path):
                    with open(rows_path) as f:
                        kept = json.load(f)
                    row.update(summary(kept["requests"], kept["window_s"],
                                       arr["clients"]))
                    lags = kept["client_lag_s"]
                    row["client_lag_max_ms"] = max(lags) * 1e3 \
                        if lags else None
                runs.append(row)
                print("# " + json.dumps(row), flush=True)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    print(json.dumps({"workload": args.workload, "seconds": args.seconds,
                      "knee_per_s": knee(runs), "runs": runs}), flush=True)
    return 0 if all(r["rc"] == 0 for r in runs) else 1


if __name__ == "__main__":
    sys.exit(main())
