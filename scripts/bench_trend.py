"""Bench trend harness (ISSUE 8, ROADMAP item 5): the round-over-round
view the repo never had.

Three rounds of kernel wins (RLC sharding, the scheduler, comb) shipped
with an empty measurement trajectory: the driver's captures sat
disconnected, one of them an rc=1 backend-init traceback, and nothing
compared rounds or flagged a regression.  This script ingests every
capture surface it is pointed at (--root):

  * ``BENCH_r*.json``      driver headline captures ({"n", "rc",
                           "parsed": {metric, value, ...}, "tail"})
  * ``MULTICHIP_r*.json``  driver multi-chip dryruns
  * ``bench_history.jsonl`` the append-only per-config history bench.py
                           and scripts/bench_report.py write the moment
                           each config completes (partial-run capture:
                           an interrupted run keeps its finished lines)

and emits (a) a per-round capture summary that flags rc!=0 rounds and
rc 0->nonzero gaps (the r04->r05 class), and (b) a per-metric trend
table with delta-vs-previous and a REGRESSION flag against the
best-known value.  Exit code is 0 — the harness reports, the operator
decides — unless --strict, which exits 1 when a regression or capture
gap is present (for CI).

Usage:
    python scripts/bench_trend.py [--root DIR] [--history FILE]
                                  [--threshold 0.05] [--json] [--strict]
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# fraction below best-known that counts as a regression (5% was past
# the noise of a chip shared over a network link; not re-measured on a
# co-located one)
DEFAULT_THRESHOLD = 0.05


def _load_json(path: str):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        return {"_error": f"{type(e).__name__}: {e}"}


def load_rounds(root: str) -> list:
    """BENCH_r*.json driver captures, round order.  A round that
    crashed (rc != 0, no parsed metric) still yields a row — the gap IS
    the signal."""
    out = []
    for path in sorted(glob.glob(os.path.join(root, "BENCH_r*.json"))):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if not m:
            continue
        doc = _load_json(path)
        parsed = doc.get("parsed") or {}
        out.append({
            "round": int(m.group(1)),
            "file": os.path.basename(path),
            "rc": doc.get("rc"),
            "metric": parsed.get("metric"),
            "value": parsed.get("value"),
            "unit": parsed.get("unit"),
            "vs_baseline": parsed.get("vs_baseline"),
            "note": parsed.get("note"),
        })
    return out


def load_multichip(root: str) -> list:
    out = []
    for path in sorted(glob.glob(os.path.join(root, "MULTICHIP_r*.json"))):
        m = re.search(r"MULTICHIP_r(\d+)\.json$", path)
        if not m:
            continue
        doc = _load_json(path)
        out.append({
            "round": int(m.group(1)),
            "file": os.path.basename(path),
            "rc": doc.get("rc"),
            "ok": doc.get("ok"),
            "n_devices": doc.get("n_devices"),
            "skipped": doc.get("skipped"),
        })
    return out


def capture_summary(rounds: list) -> list:
    """One row per round with a flag column; rc transitions 0 ->
    nonzero are called out as capture gaps (BENCH_r04 rc=0 ->
    BENCH_r05 rc=1 is the motivating instance)."""
    rows = []
    prev = None
    for r in rounds:
        flag = ""
        if r["rc"] not in (0, None):
            flag = f"CAPTURE-FAILED rc={r['rc']}"
            if prev is not None and prev["rc"] == 0:
                flag += (f" (gap: r{prev['round']:02d} rc=0 -> "
                         f"r{r['round']:02d} rc={r['rc']})")
        elif r["value"] is None:
            flag = "no parsed metric"
        elif r.get("note") and "host fallback" in str(r["note"]):
            flag = "host-fallback capture (no chip number)"
        rows.append(dict(r, flag=flag))
        prev = r
    return rows


def _series_key(rec: dict):
    """History/driver records group by metric (bench lines) or config
    label (bench_report lines)."""
    return rec.get("metric") or rec.get("config")


def _series_value(rec: dict):
    """The comparable throughput number of a record."""
    for k in ("value", "sigs_per_s"):
        v = rec.get(k)
        if isinstance(v, (int, float)):
            return float(v)
    return None


def build_series(rounds: list, history: list) -> dict:
    """key -> ordered observations [{label, value, rc, ...}] from the
    driver rounds first (round order), then history (file order =
    chronological)."""
    series: dict = {}
    for r in rounds:
        if r["metric"] is None:
            continue
        series.setdefault(r["metric"], []).append({
            "label": f"r{r['round']:02d}",
            "value": r["value"],
            "rc": r["rc"],
            "vs_baseline": r["vs_baseline"],
            "note": r.get("note"),
        })
    for rec in history:
        key = _series_key(rec)
        if key is None:
            continue
        label = rec.get("round") or rec.get("source") or "hist"
        series.setdefault(key, []).append({
            "label": str(label),
            "value": _series_value(rec),
            "rc": 0,
            "vs_baseline": rec.get("vs_baseline"),
            "note": rec.get("note"),
            # the ADR-021 device decomposition block (when the capture
            # carried one): compile_frac feeds the compile-inflation
            # exclusion in trend_rows
            "device": rec.get("device"),
            # ADR-027 mesh-scaling columns (BENCH_MESH lines): the
            # staging overlap ratio and rate_N/(N*rate_1) efficiency
            "chunk_overlap": rec.get("chunk_overlap"),
            "scaling_efficiency": rec.get("scaling_efficiency"),
        })
    return series


# first-launch compile share of the measured wall above which a round
# measures the compiler, not the pipeline (ISSUE 13 satellite: the
# decomposition finally makes this detectable — a first launch's
# compile runs tens of seconds and used to silently deflate a round's
# number)
COMPILE_INFLATION_FRAC = 0.10


def _compile_frac(o: dict):
    dev = o.get("device")
    if isinstance(dev, dict):
        return dev.get("compile_frac")
    return None


def trend_rows(obs: list, threshold: float) -> list:
    """Delta-vs-previous and regression-vs-best flags for one series.
    Host-fallback captures never count as the best-known value (they
    measure the host, not the pipeline) and are not flagged as
    regressions — they are capture failures, already called out.
    Compile-inflated captures (first-launch compile > 10% of the
    measured device wall, read from the ADR-021 `device` block) are
    excluded the same way: they measure the compiler, not the
    pipeline, and must neither set the best-known bar nor be flagged
    as regressions against it."""
    rows = []
    best = None
    prev_v = None
    for o in obs:
        flag = ""
        v = o["value"]
        fallback = o.get("note") and "host fallback" in str(o["note"])
        cfrac = _compile_frac(o)
        inflated = cfrac is not None and cfrac > COMPILE_INFLATION_FRAC
        delta = None
        if v is not None and prev_v:
            delta = 100.0 * (v - prev_v) / prev_v
        if v is None:
            flag = "CAPTURE-FAILED" if o.get("rc") not in (0, None) \
                else "no value"
        elif fallback:
            flag = "host-fallback (excluded from best)"
        elif inflated:
            flag = (f"compile-inflated {100.0 * cfrac:.0f}% of wall "
                    f"(excluded from best)")
        else:
            if best is not None and v < best * (1.0 - threshold):
                flag = (f"REGRESSION {100.0 * (1 - v / best):.1f}% "
                        f"below best")
            if best is None or v > best:
                best = v
                flag = (flag + " " if flag else "") + "best"
        rows.append(dict(o, delta_vs_prev_pct=(
            round(delta, 1) if delta is not None else None), flag=flag))
        if v is not None and not fallback and not inflated:
            prev_v = v
    return rows


def _fmt(v):
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:g}"
    return str(v)


def render(summary: list, series_rows: dict, multichip: list) -> str:
    lines = ["# bench trend", "", "## capture summary (BENCH_r*.json)"]
    lines.append(f"{'round':>6} {'rc':>3} {'metric':<34} "
                 f"{'value':>12} {'vs_base':>8}  flag")
    for r in summary:
        lines.append(
            f"{'r%02d' % r['round']:>6} {_fmt(r['rc']):>3} "
            f"{_fmt(r['metric']):<34} {_fmt(r['value']):>12} "
            f"{_fmt(r['vs_baseline']):>8}  {r['flag']}")
    for key in sorted(series_rows):
        rows = series_rows[key]
        lines += ["", f"## trend: {key}"]
        lines.append(f"{'label':>14} {'value':>12} {'delta%':>8} "
                     f"{'vs_base':>8} {'overlap':>8} {'scaleff':>8}  flag")
        for o in rows:
            lines.append(f"{o['label']:>14} {_fmt(o['value']):>12} "
                         f"{_fmt(o['delta_vs_prev_pct']):>8} "
                         f"{_fmt(o.get('vs_baseline')):>8} "
                         f"{_fmt(o.get('chunk_overlap')):>8} "
                         f"{_fmt(o.get('scaling_efficiency')):>8}  "
                         f"{o['flag']}")
    if multichip:
        lines += ["", "## multichip dryruns (MULTICHIP_r*.json)"]
        lines.append(f"{'round':>6} {'rc':>3} {'ok':>5} {'devices':>8}")
        for r in multichip:
            lines.append(f"{'r%02d' % r['round']:>6} {_fmt(r['rc']):>3} "
                         f"{_fmt(r['ok']):>5} {_fmt(r['n_devices']):>8}")
    return "\n".join(lines)


def with_prev_round_delta(line: dict, history: list) -> dict:
    """bench_report's delta-vs-previous-round columns: find the most
    recent history record for the same config/metric with a comparable
    value and annotate the delta.  Pure — bench_report calls this on
    each config line before printing/appending."""
    key = _series_key(line)
    cur = _series_value(line)
    if key is None or cur is None:
        return line
    prev = None
    for rec in history:
        if _series_key(rec) == key and _series_value(rec) is not None:
            prev = rec
    if prev is None:
        return line
    pv = _series_value(prev)
    out = dict(line)
    out["prev_sigs_per_s"] = pv
    if pv:
        out["delta_vs_prev_pct"] = round(100.0 * (cur - pv) / pv, 1)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    root_default = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    ap.add_argument("--root", default=root_default,
                    help="directory holding BENCH_r*.json (default: "
                         "repo root)")
    ap.add_argument("--history", default="",
                    help="bench_history.jsonl path (default: "
                         "$BENCH_HISTORY or <root>/bench_history.jsonl)")
    ap.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD,
                    help="regression threshold vs best-known "
                         "(default 0.05)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output")
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 on any regression or capture gap")
    args = ap.parse_args(argv)

    from bench import load_history

    rounds = load_rounds(args.root)
    multichip = load_multichip(args.root)
    if args.history:
        history = load_history(args.history)
    elif os.environ.get("BENCH_HISTORY"):
        history = load_history()  # env-directed file
    else:
        history = load_history(os.path.join(args.root,
                                            "bench_history.jsonl"))
    summary = capture_summary(rounds)
    series = build_series(rounds, history)
    series_rows = {k: trend_rows(v, args.threshold)
                   for k, v in series.items()}

    flagged = [r for r in summary if r["flag"].startswith("CAPTURE")]
    regressed = [o for rows in series_rows.values() for o in rows
                 if o["flag"].startswith("REGRESSION")]
    if args.json:
        print(json.dumps({"summary": summary, "trend": series_rows,
                          "multichip": multichip,
                          "capture_gaps": len(flagged),
                          "regressions": len(regressed)}, indent=2))
    else:
        print(render(summary, series_rows, multichip))
        if flagged or regressed:
            print(f"\n{len(flagged)} capture gap(s), "
                  f"{len(regressed)} regression flag(s)")
    if args.strict and (flagged or regressed):
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
