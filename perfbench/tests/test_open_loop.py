"""The open window of perfbench/run.py: the schedule and the accounting as
pure functions (no sleeping), the refusals, the trace reduction over a
profiled span, what the readers give on an open run; and one short run of
real client threads against a stub generator whose requests sleep a fixed
service time, under and over its capacity."""
import statistics
import types

import pytest

from perfbench import books, progspans, run, stats
from perfbench import tracered as tr
from perfbench.tests.test_progspans import reader, rec

MS = 1e6    # ns
SEED = 2**31 + 4242     # more than 32 signed bits hold


def gaps(times):
    return [b - a for a, b in zip([0.0] + times, times)]


# ---------------------------------------------------------------------------
# the schedule
# ---------------------------------------------------------------------------

def test_the_schedule_is_a_function_of_the_seed():
    a = run.schedule(SEED, 40.0, 30, 1.0)
    assert a == run.schedule(SEED, 40.0, 30, 1.0)
    assert a != run.schedule(SEED + 1, 40.0, 30, 1.0)
    assert a != run.schedule(SEED, 40.0, 30, 2.0)
    assert run.schedule(-7, 40.0, 30, 1.0) == run.schedule(-7, 40.0, 30, 1.0)


@pytest.mark.parametrize("rate,seconds,gap_cv", [
    (38.8, 30, 1.0), (31.04, 30, 2.0), (0.5, 3, 1.0), (1000.0, 10, 0.5),
    (7.3, 10, 0.0), (2.2, 1, 3.0)])
def test_the_schedule_holds_exactly_round_rate_x_seconds(rate, seconds,
                                                         gap_cv):
    for seed in (1, SEED, 10**12):
        t = run.schedule(seed, rate, seconds, gap_cv)
        assert len(t) == round(rate * seconds)
        assert all(0 <= x < seconds for x in t)
        assert t == sorted(t)


@pytest.mark.parametrize("gap_cv", [1.0, 2.0])
def test_the_gaps_spread_as_asked(gap_cv):
    g = gaps(run.schedule(SEED, 2000.0, 10, gap_cv))
    assert statistics.pstdev(g) / statistics.mean(g) == pytest.approx(
        gap_cv, rel=0.1)


def test_gap_cv_0_is_evenly_spaced():
    g = gaps(run.schedule(SEED, 10.0, 3, 0.0))
    assert g == pytest.approx([3 / 31] * 30)


# ---------------------------------------------------------------------------
# the plan and its refusals
# ---------------------------------------------------------------------------

def stub(concurrent=None):
    gen = types.SimpleNamespace(__name__="stub", request=lambda w, i: True)
    if concurrent is not None:
        gen.CONCURRENT = concurrent
    return gen


def params(**arrivals):
    arr = {"rate_per_s": 10.0, "gap_cv": 1.0, "clients": 1}
    arr.update(arrivals)
    return {"generator": "stub", "arrivals": arr}


def test_no_arrivals_key_means_the_closed_loop():
    assert run.arrivals_plan({"generator": "stub"}, stub(), SEED, 30) is None


def test_a_plan_carries_the_schedule_of_its_seed():
    plan = run.arrivals_plan(params(), stub(), SEED, 3)
    assert plan["t_sched"] == run.schedule(SEED, 10.0, 3, 1.0)
    assert (plan["rate_per_s"], plan["gap_cv"], plan["clients"]) == \
        (10.0, 1.0, 1)


def test_more_than_one_client_needs_a_concurrent_generator():
    for gen in (stub(), stub(concurrent=False), stub(concurrent=1)):
        with pytest.raises(run.Refused, match="CONCURRENT"):
            run.arrivals_plan(params(clients=2), gen, SEED, 3)
    assert run.arrivals_plan(params(clients=2), stub(concurrent=True),
                             SEED, 3)["clients"] == 2
    # one client never asks
    assert run.arrivals_plan(params(), stub(concurrent=False), SEED, 3)


@pytest.mark.parametrize("arrivals", [
    {"rate_per_s": 10.0, "gap_cv": 1.0},
    {"rate_per_s": 10.0, "gap_cv": 1.0, "clients": 1, "burst": 3},
    {"rate_per_s": 0, "gap_cv": 1.0, "clients": 1},
    {"rate_per_s": 10.0, "gap_cv": -1, "clients": 1},
    {"rate_per_s": 10.0, "gap_cv": 1.0, "clients": 0},
    {"rate_per_s": 10.0, "gap_cv": 1.0, "clients": 1.5},
    {"rate_per_s": 0.1, "gap_cv": 1.0, "clients": 1}])  # 0.3 -> 0 requests
def test_a_malformed_or_empty_plan_is_refused(arrivals):
    with pytest.raises(run.Refused):
        run.arrivals_plan({"arrivals": arrivals}, stub(), SEED, 3)


def test_a_finite_generator_must_hold_the_whole_schedule():
    plan = run.arrivals_plan(params(), stub(), SEED, 3)    # 30 requests
    run.refuse_over_capacity({}, plan)
    run.refuse_over_capacity({"capacity": 30}, plan)
    with pytest.raises(run.Refused, match="30 requests"):
        run.refuse_over_capacity({"capacity": 29}, plan)


# ---------------------------------------------------------------------------
# the accounting
# ---------------------------------------------------------------------------

def test_wall_runs_from_the_scheduled_arrival():
    t_sched = [0.1, 0.2, 0.3]
    # request 1 waited 0.15 s for the client request 0 held
    served = [(0.1, 0.35, True, 7), (0.35, 0.6, True, 7),
              (0.6, 0.7, False, 7)]
    rows = run.settle(t_sched, served, 1.0)
    assert [r["wall_s"] for r in rows] == pytest.approx([0.25, 0.4, 0.4])
    assert [r["service_s"] for r in rows] == pytest.approx([0.25, 0.25, 0.1])
    assert [r["ok"] for r in rows] == [True, True, False]
    assert not any(r["late"] for r in rows)
    assert [r["client"] for r in rows] == [7, 7, 7]
    assert [r["i"] for r in rows] == [0, 1, 2]


def test_past_the_drain_a_request_is_late_and_failed():
    seconds = 2.0
    deadline = seconds + run.DRAIN_S
    t_sched = [0.5, 1.0, 1.5, 1.9]
    served = [(0.5, deadline, True, 1),          # on the edge: in time
              (deadline - 0.1, deadline + 0.2, True, 1),  # returned late
              None,                              # never started
              None]
    rows = run.settle(t_sched, served, seconds)
    assert [r["late"] for r in rows] == [False, True, True, True]
    assert [r["ok"] for r in rows] == [True, False, False, False]
    assert [r["wall_s"] for r in rows] == pytest.approx(
        [deadline - 0.5, deadline - 1.0, deadline - 1.5, deadline - 1.9])
    assert rows[1]["t0"] == deadline - 0.1 and rows[2]["t0"] is None
    assert all(r["service_s"] is None for r in rows[1:])


def test_backlog_ratio_compares_the_window_s_last_fifth_with_its_first():
    rows = [{"t_sched": t, "wall_s": w} for t, w in
            [(0.5, 0.1), (1.0, 0.3), (1.9, 0.2), (5.0, 9.0),
             (8.0, 0.5), (9.5, 0.4)]]
    assert stats.backlog_ratio(rows, 10.0) == pytest.approx(0.45 / 0.2)
    assert stats.backlog_ratio(rows[:3], 10.0) is None


# ---------------------------------------------------------------------------
# the trace over a profiled span, and the readers of an open run
# ---------------------------------------------------------------------------

def open_planes():
    """A profiled span of 20 ms; two clients' requests overlap in it, one
    began before it; the device runs 1 + 2 + 3 ms inside it and 3 ms
    outside."""
    ops = [("early", 0.0, 2 * MS), ("kernel", 3 * MS, 2 * MS),
           ("kernel", 10 * MS, 3 * MS), ("late", 21 * MS, 2 * MS)]
    host = [("pb.profiled", 1 * MS, 20 * MS),
            ("pb.request", 0.0, 6 * MS), ("pb.request", 2 * MS, 12 * MS),
            ("pb.request", 9 * MS, 5 * MS), ("pb.light.verify", 9.5 * MS,
                                             4 * MS)]
    return {"/device:TPU:0": {"XLA Ops": ops},
            "/host:CPU": {"python": host}}


def test_an_open_trace_is_reduced_over_its_profiled_span():
    red = tr.reduce(open_planes(), bounds=run.PROFILED_SPAN)
    assert red["window_s"] == pytest.approx(20e-3)
    assert red["busy_s"] == pytest.approx(6e-3)     # 1 + 2 + 3 ms
    assert red["requests_traced"] == 2              # the two inside it
    assert "request_busy_s" not in red
    # idle 9.5-10 and 13-13.5 ms inside the verify
    assert dict(red["idle_gaps"])["pb.light.verify"] == pytest.approx(1e-3)
    # the same planes as a closed run: from the first request to the last
    closed = tr.reduce(open_planes())
    assert closed["window_s"] == pytest.approx(14e-3)
    assert len(closed["request_busy_s"]) == 3
    # no marker, no reduction
    planes = open_planes()
    planes["/host:CPU"]["python"] = planes["/host:CPU"]["python"][1:]
    assert "why" in tr.reduce(planes, bounds=run.PROFILED_SPAN)


def open_run(program=None):
    red = tr.reduce(open_planes(), bounds=run.PROFILED_SPAN)
    rows = [{"i": i, "t_sched": 0.01 * i, "t0": 0.01 * i, "wall_s": 0.006,
             "service_s": 0.006, "ok": True, "late": False, "client": 1}
            for i in range(8)]
    return {"requests": rows, "window_s": 1.0, "trace": red,
            "arrivals": {"rate_per_s": 8.0, "gap_cv": 1.0, "clients": 1},
            "spans": [("pb.request", 0.01 * i, 0.01 * i + 0.006)
                      for i in range(8)],
            "profiled": {"t_on": 0.0, "t_off": 0.02,
                         "records": [{"n": 1000, "wall_s": 0.004},
                                     {"n": 2000, "wall_s": 0.005}],
                         "requests": [1, 2]}}


def test_device_readers_read_the_profiled_span():
    r = open_run()
    assert reader("device.idle_share").read(r) == pytest.approx(70.0)
    # 6 ms busy over 3,000 real signatures
    assert reader("kernel.us_per_sig").read(r) == pytest.approx(2.0)
    r["profiled"]["records"] = []
    assert reader("kernel.us_per_sig").read(r) is None
    r["trace"] = {"why": "no TPU plane"}
    assert reader("kernel.us_per_sig").read(r) is None
    assert reader("device.idle_share").read(r) is None


def test_the_profiled_launches_are_read_when_the_profiler_stops(
        monkeypatch):
    import jax
    from tendermint_tpu.crypto import devobs

    monkeypatch.setattr(devobs, "OBS",
                        devobs.DevObs(capacity=256, enabled=True))
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)

    def launch(n):
        devobs.record({"path": "stub", "nb": 64, "n": n, "wall_s": 1e-3})

    prof = run.OpenProfiler()
    try:
        launch(1)
        prof.tick(run.TRACE_AFTER_S - 0.1, 10.0)
        assert prof.state == "idle"
        prof.tick(run.TRACE_AFTER_S, 10.0)
        assert prof.state == "on"
        for _ in range(run.TRACE_LAUNCHES - 1):
            launch(100)
        prof.tick(run.TRACE_AFTER_S + 0.1, 10.0)
        assert prof.state == "on"
        launch(100)
        prof.tick(run.TRACE_AFTER_S + 0.2, 10.0)
        assert prof.state == "done"
        for _ in range(300):        # the ring turns over after the stop
            launch(7)
        assert [r["n"] for r in prof.records] == [100] * run.TRACE_LAUNCHES
    finally:
        prof.close()
    # a window that closes before the profiler's start never starts it
    late = run.OpenProfiler()
    late.tick(run.TRACE_AFTER_S, run.TRACE_AFTER_S)
    assert late.state == "missed" and late.wake_in(run.TRACE_AFTER_S) is None
    assert "too short" in late.reduce()["why"]


@pytest.mark.parametrize("metric", [
    "entry.host_ms", "launch.wall_ms", "launch.count", "launch.stage_ms",
    "launch.stage_cpu_ms", "launch.drain_ms", "launch.h2d_ms",
    "launch.head_ms", "launch.pub_rows_hit_share", "sched.queue_wait_ms",
    "sched.lanes_per_launch", "lanes.ed25519_ms", "light.hash_ms",
    "entry.unspanned_ms", "votes.add_ms", "route.outside_ms"])
def test_per_request_readers_say_nothing_on_an_open_run(monkeypatch, metric):
    # the program's recorder holds a span in every request's interval
    records = [rec("valset.hash", 10 * i + 1, 2) for i in range(8)]
    monkeypatch.setattr(progspans, "program_records",
                        lambda: (records, False))
    r = open_run()
    assert progspans.by_request(r) is None
    assert books.requests(r) is None
    assert reader(metric).read(r) is None
    # the same rows as a closed run do carry the span
    del r["arrivals"]
    assert progspans.by_request(r) is not None


def test_end_to_end_readers_of_an_open_run():
    from perfbench.tests.test_progspans import LAYERS
    import importlib.util
    import os

    def e2e(name):
        spec = importlib.util.spec_from_file_location(
            "e2e_" + name.replace(".", "_"),
            os.path.join(os.path.dirname(LAYERS), "end_to_end",
                         name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    rows = run.settle([0.1, 0.2, 0.3, 0.9], [
        (0.1, 0.15, True, 1), (0.15, 0.3, True, 1), (0.3, 0.35, True, 1),
        None], 1.0)
    r = {"requests": rows, "window_s": 1.0, "arrivals": {}}
    # the offered rate, late requests and all
    assert e2e("requests_per_s")(r) == pytest.approx(4.0)
    # walls 50, 100, 50 ms and the late one's 1 + DRAIN_S - 0.9 s
    assert e2e("request_ms.p50")(r) == pytest.approx(75.0)


# ---------------------------------------------------------------------------
# real client threads
# ---------------------------------------------------------------------------

SERVICE_S = 0.02


def sleeper(concurrent=False):
    import time

    def request(world, i):
        time.sleep(SERVICE_S)
        return True
    gen = types.SimpleNamespace(__name__="sleeper", request=request)
    if concurrent:
        gen.CONCURRENT = True
    return gen


def serve(rate, seconds, clients=1, concurrent=False):
    gen = sleeper(concurrent)
    plan = run.arrivals_plan(
        {"arrivals": {"rate_per_s": rate, "gap_cv": 0.0,
                      "clients": clients}}, gen, SEED, seconds)
    return run.open_window(gen, {}, seconds, run.Spans(False), False, plan)


def test_under_capacity_every_request_is_served_near_its_service_time():
    # one client serves 50/s; 25/s arrive, evenly spaced
    r = serve(25.0, 2.0)
    rows = r["requests"]
    assert len(rows) == 50 and r["window_s"] == 2.0
    assert not any(x["late"] for x in rows) and all(x["ok"] for x in rows)
    assert len({x["client"] for x in rows}) == 1
    assert all(x["service_s"] >= SERVICE_S for x in rows)
    # no backlog: loose enough for a host that runs six test workers
    assert stats.median(x["wall_s"] for x in rows) < 4 * SERVICE_S
    assert stats.backlog_ratio(rows, 2.0) < 2.5
    assert "profiled" not in r and r["trace"] is None


def test_over_capacity_the_queue_grows_and_the_drain_cuts_it(monkeypatch):
    # one client serves 50/s; 100/s arrive for 2 s, and 0.5 s of drain
    monkeypatch.setattr(run, "DRAIN_S", 0.5)
    r = serve(100.0, 2.0)
    rows = r["requests"]
    assert len(rows) == 200
    late = [x for x in rows if x["late"]]
    assert len(late) >= 50                 # at most 125 can be served
    assert not any(x["ok"] for x in late)
    assert all(x["wall_s"] == pytest.approx(2.5 - x["t_sched"])
               for x in late)
    served = [x for x in rows if not x["late"]]
    half = len(served) // 2
    assert stats.median(x["wall_s"] for x in served[half:]) > \
        2 * stats.median(x["wall_s"] for x in served[:half])
    assert stats.backlog_ratio(rows, 2.0) > 1.25


def test_clients_serve_side_by_side():
    # 100/s is twice what one client serves; four serve it with room
    r = serve(100.0, 1.0, clients=4, concurrent=True)
    rows = r["requests"]
    assert len(rows) == 100 and not any(x["late"] for x in rows)
    assert len({x["client"] for x in rows}) > 1
    assert stats.median(x["wall_s"] for x in rows) < 4 * SERVICE_S


def test_a_request_that_raises_fails_the_run():
    def request(world, i):
        if i == 3:
            raise ValueError("request 3")
        return True
    gen = types.SimpleNamespace(__name__="raiser", request=request)
    plan = run.arrivals_plan(
        {"arrivals": {"rate_per_s": 20.0, "gap_cv": 0.0, "clients": 1}},
        gen, SEED, 1.0)
    with pytest.raises(ValueError, match="request 3"):
        run.open_window(gen, {}, 1.0, run.Spans(False), False, plan)
