#!/usr/bin/env python3
"""perfbench/run.py — one cell of tpu-bft's benchmark, in one process.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in BENCHMARK.json: its configuration is
perfbench/configs/<config>.json, its traffic mix perfbench/workloads/
<traffic>.json, whose `generator` names the module perfbench/traffic/
<generator>.py.  The metrics the cell reports are those BENCHMARK.json
lists for it; each is read by a file of its own, perfbench/end_to_end/
<metric>.py or perfbench/layers/<metric>.py.  This file knows no cell,
configuration, traffic mix or metric by name (perfbench/README.md).

A run: data from --seed; the process brought up as the configuration says;
every shape the cell can reach warmed; correctness checked against
per-signature OpenSSL; the timed window; the gate.  Everything up to the
first timed request is `setup_s`.  The last line of stdout is the result
object and nothing else; lines before it start with `# `.

It refuses to start (exit 2, reason on stderr, nothing on stdout) unless
JAX's platform is `tpu` with the chips the cell asks for, no TM_TPU_*
variable steers the path, and the native staging library and OpenSSL are
there.  A CPU is never a fallback.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import faulthandler  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
DATA_DIRS = [HERE]
WATCHDOG_S = 1150         # a cold first run may take 1200 s; a hang must
#                           leave a traceback, not a silent timeout
TRACE_AFTER_S = 2.0       # --trace 1: the profiler starts this far into
TRACE_FOR_S = 3.0         # the window and runs this long, or until
TRACE_LAUNCHES = 24       # this many launches are in it if that is sooner
#                           (always whole requests, at least one): a comb
#                           launch is ~30,000 device events, and the first
#                           traced run of val150-live wrote 300 MB in 3 s
#                           and took 200 s to stop and read


class Refused(Exception):
    """A reason not to start; exit 2, nothing on stdout."""


# ---------------------------------------------------------------------------
# finding the cell's files by the names in the manifest
# ---------------------------------------------------------------------------

def load_json(*parts) -> dict:
    """The first <base>/<parts> there is; DATA_DIRS is perfbench/ alone,
    and perfbench/tests adds its fixtures."""
    for base in DATA_DIRS:
        path = os.path.join(base, *parts)
        if os.path.exists(path):
            with open(path) as f:
                return json.load(f)
    raise Refused(f"no {os.path.join(*parts)} under {DATA_DIRS}")


def load_cell(manifest_path: str, name: str) -> dict:
    try:
        with open(manifest_path) as f:
            manifest = json.load(f)
    except OSError as e:
        raise Refused(f"cannot read {manifest_path}: {e}")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in {manifest_path} "
                      f"(it has {sorted(cells)})")
    cell = dict(cells[name])
    for kind in ("end_to_end", "per_layer"):
        cell[kind] = [m for m in manifest[kind]
                      if name in m.get("workloads", [name])]
    cell["config_file"] = load_json("configs", cell["config"] + ".json")
    cell["params"] = load_json("workloads", cell["traffic"] + ".json")
    return cell


def load_reader(kind_dir: str, metric: str):
    """The `read(run)` of perfbench/<kind_dir>/<metric>.py."""
    path = os.path.join(HERE, kind_dir, metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "perfbench_reader_" + metric.replace(".", "_").replace("-", "_"),
        path)
    if spec is None or not os.path.exists(path):
        raise Refused(f"metric {metric!r} has no reader at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(kind_dir: str, metrics: list, run: dict) -> dict:
    """A reader that finds nothing to read returns None and its metric is
    left out of the line."""
    out = {}
    for m in metrics:
        value = load_reader(kind_dir, m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# start-up
# ---------------------------------------------------------------------------

def startup(chips: int):
    """Refuse, or return (jax's devices, the device dict of the result)."""
    from perfbench import data

    reasons = data.refusals(os.environ)
    if reasons:
        raise Refused("; ".join(reasons))
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise Refused(f"JAX found no backend: {e}")
    if devices[0].platform != "tpu":
        raise Refused(f"JAX platform is {devices[0].platform!r}, not 'tpu' "
                      f"(no accelerator here)")
    if len(devices) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX has "
                      f"{len(devices)}")
    return devices


def program_checks():
    try:
        import tendermint_tpu  # noqa: F401 - places the compile cache
    except ImportError as e:
        raise Refused(f"the program is not here beside the benchmark: {e}")
    from tendermint_tpu.crypto import ed25519 as edkeys
    from tendermint_tpu.libs import native
    if native.get_lib() is None:
        raise Refused("the native staging library did not build "
                      "(tendermint_tpu/native/*.c)")
    if not edkeys._HAVE_OSSL:
        raise Refused("the cryptography package (OpenSSL) is missing")


def say(msg: str):
    print(f"# [{time.perf_counter() - T_START:7.2f}s] {msg}", flush=True)


# ---------------------------------------------------------------------------
# spans: the benchmark's own, around each request and each call into a layer
# ---------------------------------------------------------------------------

class Spans:
    """Off (--trace 0): `span` costs one attribute read.  On: each span is
    kept as (name, start, end) on the host clock and, through
    jax.profiler.TraceAnnotation, lands in the profiler's trace on the
    trace's own clock while one is being taken."""

    def __init__(self, on: bool):
        self.on = on
        self.rows = []
        self._null = contextlib.nullcontext()

    def span(self, name: str):
        return self._span("pb." + name) if self.on else self._null

    @contextlib.contextmanager
    def _span(self, name):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                self.rows.append((name, t0, time.perf_counter()))


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

class Profiler:
    """A profiler trace of a few seconds of the steady state, started and
    stopped from the main thread between two requests, written outside the
    checkout, reduced and removed."""

    def __init__(self):
        self.dir = None
        self.first = self.last = None     # request indices traced
        self.state = "idle"
        self.t_on = 0.0
        self.seq_on = 0

    def tick(self, i: int, since_begin: float):
        """Called before request i."""
        import jax
        from tendermint_tpu.crypto import devobs
        if self.state == "idle" and since_begin >= TRACE_AFTER_S:
            self.dir = tempfile.mkdtemp(prefix="perfbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # spans, not every call
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.state, self.first = "on", i
            self.t_on, self.seq_on = time.perf_counter(), devobs.last_seq()
        elif self.state == "on" and i > self.first and (
                devobs.last_seq() - self.seq_on >= TRACE_LAUNCHES
                or time.perf_counter() - self.t_on >= TRACE_FOR_S):
            self.stop(i)

    def stop(self, i: int):
        import jax
        if self.state == "on":
            jax.profiler.stop_trace()
            self.state, self.last = "done", i - 1

    def reduce(self) -> dict:
        from perfbench import tracered
        if self.state != "done":
            return {"why": "the window was too short for the profiler "
                           f"(it starts {TRACE_AFTER_S} s in)"}
        path = tracered.find_xplane(self.dir)
        size = os.path.getsize(path)
        planes = tracered.read_xplane(path)
        say(f"trace {size} bytes; planes/lines/events "
            + json.dumps(tracered.inventory(planes))[:1500])
        red = tracered.reduce(planes)
        red["requests"] = [self.first, self.last]
        return red

    def close(self):
        """Whatever happened: no trace running, no trace left behind."""
        import jax
        if self.state == "on":
            jax.profiler.stop_trace()
            self.state = "abandoned"
        if self.dir is not None:
            shutil.rmtree(self.dir, ignore_errors=True)


def _buckets(devobs) -> set:
    """The (path/bucket) pairs launched so far in this process: one that
    appears during the window was launched cold inside it, wherever in the
    window that was."""
    return {f"{e['path']}/nb={e['nb']}" for e in devobs.compile_inventory()}


def window(gen, world: dict, seconds: float, spans: Spans, trace: bool):
    """The closed loop: one caller, the next request when the last has
    returned.  Returns the run record the metric readers take."""
    from tendermint_tpu.crypto import devobs
    from tendermint_tpu.crypto import scheduler as vsched

    prof = Profiler() if trace else None
    requests = []
    capacity = world.get("capacity")
    seq_begin = devobs.last_seq()
    buckets_begin = _buckets(devobs)
    now = time.perf_counter
    try:
        t_begin = now()
        i = 0
        while True:
            t0 = now()
            if t0 - t_begin >= seconds:
                break
            if capacity is not None and i >= capacity:
                raise RuntimeError(
                    f"the window outran its data: {capacity} requests were "
                    f"made in set-up and {t0 - t_begin:.1f} of {seconds} s "
                    f"are gone (raise `max_requests_per_s` in a new traffic "
                    f"file)")
            if prof is not None:
                prof.tick(i, t0 - t_begin)
                t0 = now()
            seq0 = devobs.last_seq()
            with spans.span("request"):
                ok = gen.request(world, i)
            t1 = now()
            row = {"i": i, "t0": t0 - t_begin, "wall_s": t1 - t0,
                   "ok": bool(ok)}
            if trace:
                # devobs' ring holds 256 launches and the scheduler keeps
                # its last window only: both are read per request, never
                # at the end
                row["records"] = devobs.records(since_seq=seq0)
                row["sched"] = vsched.last_latency_report() \
                    if vsched.running() is not None else None
            requests.append(row)
            i += 1
        window_s = now() - t_begin
        if prof is not None:
            prof.stop(i)
        # the gate reads every launch of the window; untraced, that is the
        # buckets first met in it plus one read of the ring's tail (the
        # last 256 launches) for a compile that a met bucket still paid
        tail = devobs.records(since_seq=seq_begin)
        run = {"requests": requests, "window_s": window_s,
               "launches_in_window": devobs.last_seq() - seq_begin,
               "new_buckets": sorted(_buckets(devobs) - buckets_begin),
               "window_records": [r for row in requests
                                  for r in row.get("records", [])] or tail,
               "spans": spans.rows,
               "trace": prof.reduce() if prof is not None else None}
    finally:
        if prof is not None:
            prof.close()
    return run


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def device_dict(devices, chips: int, run: dict) -> dict:
    peaks = []
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": max(peaks)}
    red = run.get("trace") or {}
    if "busy_s" in red:
        out["busy_s"] = red["busy_s"]
        out["window_s"] = red["window_s"]
    return out


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             devices) -> dict:
    from perfbench import bringup, data, stats
    from tendermint_tpu.crypto import degrade

    import jax

    config, params = cell["config_file"], cell["params"]
    gen = importlib.import_module("perfbench.traffic." + params["generator"])
    say(f"device {devices[0].device_kind} x{len(devices)}; compile cache "
        f"{jax.config.jax_compilation_cache_dir}")
    spans = Spans(trace)
    world = gen.setup(config, params, seed, seconds)
    world["span"] = spans.span
    say(f"data built: {world.get('made')}")
    stop = bringup.PROCESSES[config["process"]](world)
    try:
        say("process up as " + config["process"])
        gen.warm(world)
        say("warm")
        check_failures = list(gen.check(world))
        say(f"checked against the oracle: {check_failures or 'equal'}")
        rt = degrade.runtime()
        # what set-up made is the harness's, not the program's: out of the
        # collector's sight, so that no full collection walks it mid-window
        gc.collect()
        gc.freeze()
        if hasattr(gen, "window_begin"):
            gen.window_begin(world)
        setup_s = time.perf_counter() - T_START
        run = window(gen, world, seconds, spans, trace)
        if hasattr(gen, "window_end"):
            check_failures += gen.window_end(world, run)
    finally:
        stop()
        if "close" in world:
            world["close"]()
    run["setup_s"] = setup_s
    gate_failures = data.gate(rt, run["window_records"], run["new_buckets"])
    walls = [r["wall_s"] for r in run["requests"]]
    routes = sorted({f"{r['path']}/{r['nb']}" for r in run["window_records"]})
    say(f"window {run['window_s']:.3f} s, {len(walls)} requests, "
        f"{run['launches_in_window']} launches, routes {routes}, "
        f"compiles in window "
        f"{sum(1 for r in run['window_records'] if r.get('compile_s'))}")
    if walls:
        say("request wall s: min/q1/median/q3/p95/max " + "/".join(
            f"{stats.percentile(walls, q):.5f}"
            for q in (0, 25, 50, 75, 95, 100)) + "; slowest (i, at s, wall s) "
            + ", ".join(f"({r['i']}, {r['t0']:.2f}, {r['wall_s']:.4f})"
                        for r in sorted(run["requests"],
                                        key=lambda r: -r["wall_s"])[:3]))
    for why in check_failures:
        print(f"perfbench: check: {why}", file=sys.stderr)
    for why in gate_failures:
        print(f"perfbench: gate: {why}", file=sys.stderr)
    failed = sum(1 for r in run["requests"] if not r["ok"])
    if trace:
        metrics = read_metrics("layers", cell["per_layer"], run)
    else:
        metrics = read_metrics("end_to_end", cell["end_to_end"], run)
    result = {"correct": not check_failures and not gate_failures,
              "attempted": len(run["requests"]), "failed": failed,
              "metrics": metrics,
              "device": device_dict(devices, cell["chips"], run)}
    red = run.get("trace") or {}
    if trace:
        say("trace reduction: " + json.dumps(
            {k: v for k, v in red.items() if k != "request_busy_s"}))
        if "busy_s" not in red and devices[0].platform == "tpu":
            # on the chip a traced run without device time is a fault of
            # the run; a CPU rehearsal reports the device metrics as absent
            raise RuntimeError("traced run: " + red["why"])
        if "device_ops" in red:
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    try:
        cell = load_cell(MANIFEST, args.workload)
        devices = startup(cell["chips"])
        program_checks()
    except Refused as e:
        print(f"perfbench: refusing to start: {e}", file=sys.stderr)
        return 2
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices)
    faulthandler.cancel_dump_traceback_later()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
