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

The window is a closed loop (one caller, the next request when the last has
returned) unless the traffic file holds `arrivals`: then it is an open loop,
a schedule of arrivals drawn from --seed and served by client threads, each
request timed from its scheduled arrival (`open_window`).

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
import hashlib  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

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
DRAIN_S = 5.0             # an open window: a request scheduled inside it
#                           that has not returned this long after its
#                           close is late, and failed
PROFILED_SPAN = "pb.profiled"   # an open window's traced span, in the trace
ARRIVAL_KEYS = {"rate_per_s", "gap_cv", "clients"}


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

    def _start(self):
        import jax
        from tendermint_tpu.crypto import devobs
        self.dir = tempfile.mkdtemp(prefix="perfbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # spans, not every call
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.state = "on"
        self.t_on, self.seq_on = time.perf_counter(), devobs.last_seq()

    def tick(self, i: int, since_begin: float):
        """Called before request i."""
        from tendermint_tpu.crypto import devobs
        if self.state == "idle" and since_begin >= TRACE_AFTER_S:
            self._start()
            self.first = i
        elif self.state == "on" and i > self.first and (
                devobs.last_seq() - self.seq_on >= TRACE_LAUNCHES
                or time.perf_counter() - self.t_on >= TRACE_FOR_S):
            self.stop(i)

    def stop(self, i: int):
        import jax
        if self.state == "on":
            jax.profiler.stop_trace()
            self.state, self.last = "done", i - 1

    def _planes(self):
        from perfbench import tracered
        path = tracered.find_xplane(self.dir)
        size = os.path.getsize(path)
        planes = tracered.read_xplane(path)
        say(f"trace {size} bytes; planes/lines/events "
            + json.dumps(tracered.inventory(planes))[:1500])
        return planes

    def reduce(self) -> dict:
        from perfbench import tracered
        if self.state != "done":
            return {"why": "the window was too short for the profiler "
                           f"(it starts {TRACE_AFTER_S} s in)"}
        red = tracered.reduce(self._planes())
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
# the open loop: a traffic file with `arrivals`
# ---------------------------------------------------------------------------

def schedule(seed: int, rate: float, seconds: float, gap_cv: float) -> list:
    """The arrival times of an open window, in seconds from its start: a
    pure function of its arguments.  Exactly round(rate x seconds) of them
    in (0, seconds), so that the offered rate does not spread from seed to
    seed: N + 1 gaps drawn from a Gamma distribution whose coefficient of
    variation is `gap_cv` (1 is a Poisson process, over 1 bursts; 0 is
    evenly spaced), scaled so that they span the window, the N arrivals
    between them."""
    import numpy as np

    n = round(rate * seconds)
    if gap_cv == 0:
        return [seconds * (k + 1) / (n + 1) for k in range(n)]
    digest = hashlib.sha256(b"perfbench/arrivals/%d" % seed).digest()
    rng = np.random.default_rng(int.from_bytes(digest[:16], "big"))
    gaps = rng.gamma(1.0 / gap_cv ** 2, 1.0, n + 1)
    return (np.cumsum(gaps)[:n] * (seconds / gaps.sum())).tolist()


def arrivals_plan(params: dict, gen, seed: int, seconds: float):
    """None for a traffic file without `arrivals` (the closed loop); else
    the open window's plan, with its schedule.  Refuses a malformed key,
    an empty schedule, and more than one client for a generator that does
    not declare `CONCURRENT = True`."""
    arr = params.get("arrivals")
    if arr is None:
        return None
    if not isinstance(arr, dict) or set(arr) != ARRIVAL_KEYS:
        raise Refused(f"`arrivals` must hold exactly {sorted(ARRIVAL_KEYS)}, "
                      f"not {arr!r}")
    rate, gap_cv, clients = arr["rate_per_s"], arr["gap_cv"], arr["clients"]
    if not (isinstance(clients, int) and clients >= 1 and rate > 0
            and gap_cv >= 0):
        raise Refused(f"`arrivals` needs rate_per_s > 0, gap_cv >= 0 and a "
                      f"whole number of clients >= 1: {arr!r}")
    if clients > 1 and getattr(gen, "CONCURRENT", False) is not True:
        raise Refused(f"{clients} clients, and the generator "
                      f"{gen.__name__} does not declare CONCURRENT = True")
    t_sched = schedule(seed, rate, seconds, gap_cv)
    if not t_sched:
        raise Refused(f"{rate}/s over {seconds} s schedules no request")
    return {**arr, "t_sched": t_sched}


def refuse_over_capacity(world: dict, plan: dict):
    """An open window schedules all its requests before it opens: a
    generator that made a finite number of them in set-up must hold them
    all."""
    capacity = world.get("capacity")
    if capacity is not None and capacity < len(plan["t_sched"]):
        raise Refused(f"the schedule holds {len(plan['t_sched'])} requests "
                      f"and set-up made {capacity}")


def settle(t_sched: list, served: list, seconds: float) -> list:
    """The rows of an open window, a pure function: `served[i]` is
    (start, return, verdict as expected?, client thread) of request i in
    seconds from the window's start, or None where it never started.
    Latency runs from the SCHEDULED arrival, so the wait behind busy
    clients counts.  A request that has not returned by seconds + DRAIN_S
    is late and failed, its wall cut there."""
    deadline = seconds + DRAIN_S
    rows = []
    for i, (ts, s) in enumerate(zip(t_sched, served)):
        if s is not None and s[1] <= deadline:
            t0, t1, ok, tid = s
            rows.append({"i": i, "t_sched": ts, "t0": t0, "wall_s": t1 - ts,
                         "service_s": t1 - t0, "ok": bool(ok),
                         "late": False, "client": tid})
        else:
            rows.append({"i": i, "t_sched": ts,
                         "t0": None if s is None else s[0],
                         "wall_s": deadline - ts, "service_s": None,
                         "ok": False, "late": True,
                         "client": None if s is None else s[3]})
    return rows


class OpenProfiler(Profiler):
    """The profiler in an open window: started and stopped by the main
    thread at set times, whatever requests are in flight then.  The span
    it covers is marked in the trace by PROFILED_SPAN, which bounds the
    reduction."""

    def __init__(self):
        super().__init__()
        self.mark = None
        self.t_off = 0.0
        self.records = []       # the launches that ended in the span

    def tick(self, since_begin: float, seconds: float):
        from tendermint_tpu.crypto import devobs
        if self.state == "idle" and since_begin >= seconds:
            self.state = "missed"
        elif self.state == "idle" and since_begin >= TRACE_AFTER_S:
            import jax
            self._start()
            self.mark = jax.profiler.TraceAnnotation(PROFILED_SPAN)
            self.mark.__enter__()
        elif self.state == "on" and (
                devobs.last_seq() - self.seq_on >= TRACE_LAUNCHES
                or time.perf_counter() - self.t_on >= TRACE_FOR_S
                or since_begin >= seconds):
            self.stop()

    def wake_in(self, since_begin: float):
        """Seconds until the next tick has something to do; None if
        nothing."""
        if self.state == "idle":
            return max(TRACE_AFTER_S - since_begin, 0.0)
        return 0.002 if self.state == "on" else None

    def stop(self, i=None):
        import jax
        from tendermint_tpu.crypto import devobs
        if self.state == "on":
            self.mark.__exit__(None, None, None)
            self.t_off = time.perf_counter()
            # read now: devobs' ring holds the newest 256 launches only
            self.records = devobs.records(since_seq=self.seq_on)
            jax.profiler.stop_trace()
            self.state = "done"

    def reduce(self) -> dict:
        from perfbench import tracered
        if self.state != "done":
            return {"why": "the window was too short for the profiler "
                           f"(it starts {TRACE_AFTER_S} s in)"}
        return tracered.reduce(self._planes(), bounds=PROFILED_SPAN)

    def close(self):
        if self.state == "on":
            self.mark.__exit__(None, None, None)
        super().close()


def open_window(gen, world: dict, seconds: float, spans: Spans, trace: bool,
                plan: dict):
    """The open loop: `plan["clients"]` client threads stand for a
    server's handler pool and take the scheduled arrivals in order; a
    request starts at its scheduled time or when a client is free,
    whichever is later.  The main thread keeps the window's clock and the
    profiler, so a slow start or stop of the profiler delays no arrival.
    No arrival is scheduled at or after `seconds`; what has not returned
    by seconds + DRAIN_S is late (`settle`).  Returns the run record the
    metric readers take: its rows carry no launch records (a launch that
    serves several requests belongs to none of them); a traced run has
    `profiled`, the launch records and the requests of the profiled
    span."""
    from tendermint_tpu.crypto import devobs

    t_sched = plan["t_sched"]
    n = len(t_sched)
    deadline = seconds + DRAIN_S
    served = [None] * n
    lags = []           # how late a client that waited for its arrival woke
    errors = []
    halt = threading.Event()    # the main thread gave up: take no more
    cv = threading.Condition()
    taken = [0, 0]      # [next arrival to take, requests returned]
    now = time.perf_counter
    t_begin = 0.0

    def client():
        tid = threading.get_ident()
        while True:
            with cv:
                i = taken[0]
                if i >= n or errors:
                    return
                taken[0] = i + 1
            wait = t_begin + t_sched[i] - now()
            if wait > 0:
                time.sleep(wait)
            if halt.is_set():
                return
            t0 = now() - t_begin
            if t0 >= deadline:
                continue
            try:
                with spans.span("request"):
                    ok = gen.request(world, i)
            except BaseException as e:  # noqa: BLE001 - raised on main
                with cv:
                    errors.append(e)
                    cv.notify()
                return
            t1 = now() - t_begin
            with cv:
                served[i] = (t0, t1, ok, tid)
                if wait > 0:
                    lags.append(t0 - t_sched[i])
                taken[1] += 1
                cv.notify()

    prof = OpenProfiler() if trace else None
    threads = [threading.Thread(target=client, daemon=True,
                                name=f"perfbench-client-{k}")
               for k in range(plan["clients"])]
    seq_begin = devobs.last_seq()
    buckets_begin = _buckets(devobs)
    try:
        t_begin = now()
        for t in threads:
            t.start()
        while True:
            since = now() - t_begin
            if prof is not None:
                prof.tick(since, seconds)
            wake = deadline - since
            if prof is not None and prof.wake_in(since) is not None:
                wake = min(wake, prof.wake_in(since))
            with cv:
                if taken[1] >= n or errors or since >= deadline:
                    break
                cv.wait(timeout=wake)
        if prof is not None:
            prof.stop()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        rows = settle(t_sched, served, seconds)
        run = {"requests": rows, "window_s": seconds,
               "launches_in_window": devobs.last_seq() - seq_begin,
               "new_buckets": sorted(_buckets(devobs) - buckets_begin),
               "window_records": devobs.records(since_seq=seq_begin),
               "spans": spans.rows,
               "arrivals": {k: plan[k] for k in sorted(ARRIVAL_KEYS)},
               "client_lag_s": sorted(lags),
               "trace": prof.reduce() if prof is not None else None}
        if prof is not None and prof.state == "done":
            t_on, t_off = prof.t_on - t_begin, prof.t_off - t_begin
            run["profiled"] = {
                "t_on": t_on, "t_off": t_off, "records": prof.records,
                "requests": [r["i"] for r in rows if not r["late"]
                             and r["t0"] >= t_on
                             and r["t_sched"] + r["wall_s"] <= t_off]}
    finally:
        halt.set()
        if prof is not None:
            prof.close()
    return run


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def say_open(run: dict):
    """What an open window did: the offered load, the latencies from the
    scheduled arrival, the service times, how late the clients woke for an
    arrival they were free for, and the backlog."""
    from perfbench import stats

    rows, arr = run["requests"], run["arrivals"]
    walls = [r["wall_s"] for r in rows]
    service = [r["service_s"] for r in rows if not r["late"]]
    lags = run["client_lag_s"]

    def qs(v):
        return "/".join(f"{stats.percentile(v, q):.5f}"
                        for q in (0, 50, 95, 100)) if v else "-"
    say(f"open loop: {len(rows)} arrivals at {arr['rate_per_s']}/s, gap cv "
        f"{arr['gap_cv']}, {arr['clients']} client(s); late "
        f"{sum(r['late'] for r in rows)}; backlog ratio "
        f"{stats.backlog_ratio(rows, run['window_s'])}")
    say(f"wall from the scheduled arrival s: min/median/p95/max {qs(walls)}; "
        f"service s {qs(service)}; client wake-up lag s {qs(lags)}")


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
    plan = arrivals_plan(params, gen, seed, seconds)
    world = gen.setup(config, params, seed, seconds)
    world["span"] = spans.span
    say(f"data built: {world.get('made')}")
    stop = bringup.PROCESSES[config["process"]](world)
    try:
        if plan is not None:
            refuse_over_capacity(world, plan)
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
        if plan is None:
            run = window(gen, world, seconds, spans, trace)
        else:
            run = open_window(gen, world, seconds, spans, trace, plan)
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
    if plan is not None:
        say_open(run)
    elif walls:
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
        faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
        try:
            result = run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), devices)
        finally:
            faulthandler.cancel_dump_traceback_later()
    except Refused as e:
        print(f"perfbench: refusing to start: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
