"""The trace reduction on a hand-made event list: busy union, idle share,
self times, per-request attribution, idle gaps by host span."""
import importlib.util
import os

import pytest

from perfbench import tracered as tr

MS = 1e6    # ns


def planes():
    """Two requests of 10 ms each, 2 ms apart.  Request 0: a 4 ms `while`
    with two nested 1 ms bodies, then a 1 ms op overlapping a 2 ms op by
    half.  Request 1: one 3 ms op.  The module and step lines hold the
    same time again and must not be added in."""
    ops = [("while", 1 * MS, 4 * MS), ("body", 1.5 * MS, 1 * MS),
           ("body", 3 * MS, 1 * MS),
           ("copy", 6 * MS, 2 * MS), ("fusion", 7 * MS, 1.5 * MS),
           ("kernel", 14 * MS, 3 * MS)]
    host = [("pb.request", 0.0, 10 * MS), ("pb.preverify", 0.5 * MS, 5 * MS),
            ("pb.apply", 5.5 * MS, 4 * MS),
            ("pb.request", 12 * MS, 10 * MS),
            ("pb.verify_commit", 13 * MS, 8 * MS),
            ("PjitFunction(f)", 1 * MS, 1 * MS)]
    return {
        "/device:TPU:0": {
            "XLA Ops": ops,
            "XLA Modules": [("jit_f", 1 * MS, 8 * MS), ("jit_g", 14 * MS, 3 * MS)],
            "Steps": [("0", 0.0, 22 * MS)]},
        "/host:CPU": {"python": host},
    }


def test_busy_is_the_union_of_the_ops_line_only():
    red = tr.reduce(planes())
    assert red["read"] == {"planes": ["/device:TPU:0"], "line": "XLA Ops",
                           "events": 6}
    assert red["window_s"] == pytest.approx(22e-3)
    # 4 (while) + 2.5 (copy U fusion) + 3 (kernel) ms
    assert red["busy_s"] == pytest.approx(9.5e-3)
    assert red["requests_traced"] == 2
    assert red["request_busy_s"] == pytest.approx([6.5e-3, 3e-3])


def test_self_times_do_not_count_a_nested_body_twice():
    ops = dict(tr.reduce(planes())["device_ops"])
    assert ops["while"] == pytest.approx(2e-3)      # 4 ms less two bodies
    assert ops["body"] == pytest.approx(2e-3)
    assert ops["kernel"] == pytest.approx(3e-3)
    # overlapping siblings each keep their own duration
    assert ops["copy"] == pytest.approx(2e-3)
    assert ops["fusion"] == pytest.approx(1.5e-3)


def test_idle_gaps_go_to_the_innermost_host_span_open_then():
    gaps = dict(tr.reduce(planes())["idle_gaps"])
    # idle: 0-1 (0.5 request, 0.5 preverify), 5-6 (0.5 preverify, 0.5
    # apply), 8.5-14 (1 apply, 0.5 request, 2 between, 1 request, 1
    # verify_commit), 17-22 (4 verify_commit, 1 request)
    assert gaps["pb.preverify"] == pytest.approx(1e-3)
    assert gaps["pb.apply"] == pytest.approx(1.5e-3)
    assert gaps["between requests"] == pytest.approx(2e-3)
    assert gaps["pb.verify_commit"] == pytest.approx(5e-3)
    assert gaps["pb.request"] == pytest.approx(3e-3)
    assert sum(gaps.values()) == pytest.approx(22e-3 - 9.5e-3)


def test_no_tpu_plane_gives_no_device_number():
    p = planes()
    p["/device:CPU:0"] = p.pop("/device:TPU:0")
    red = tr.reduce(p)
    assert "busy_s" not in red and "holds no /device:TPU:" in red["why"]
    assert red["requests_traced"] == 2


def _reader(name):
    path = os.path.join(os.path.dirname(tr.__file__), "layers", name + ".py")
    spec = importlib.util.spec_from_file_location("r", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_device_readers_take_the_reduction_and_nothing_else():
    red = tr.reduce(planes())
    red["requests"] = [4, 5]
    rows = [{"i": i, "wall_s": 0.01, "records": [{"n": 150, "wall_s": .004}]}
            for i in range(8)]
    run = {"requests": rows, "trace": red}
    assert _reader("device.idle_share")(run) == pytest.approx(
        100 * (1 - 9.5 / 22))
    # 9.5 ms busy inside the two traced requests over their 300 rows
    assert _reader("kernel.us_per_sig")(run) == pytest.approx(9.5e3 / 300)
    assert _reader("entry.host_ms")(run) == pytest.approx(6.0)
    cpu = {"requests": rows, "trace": {"why": "no TPU plane"}}
    assert _reader("device.idle_share")(cpu) is None
    assert _reader("kernel.us_per_sig")(cpu) is None
    assert _reader("launch.wall_ms")({"requests": [{"wall_s": 1}]}) is None
