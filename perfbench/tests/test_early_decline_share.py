"""The reader of `route.early_decline_share` on hand-made spans: a share
over the `early` attributes of the declined `comb.resolve` spans; None
where no such span carries one (the parent's program), where nothing is
declined (the cells whose tables are resident) and under
progspans.MIN_REQUESTS; and the runner finds the reader by its file's
name."""
import pytest

from perfbench import progspans
from perfbench.tests.test_progspans import (  # noqa: F401  (fixture)
    program, reader, rec, run_of)

METRIC = "route.early_decline_share"


def resolved(ts_ms, outcome, early=None, dur_ms=0.05):
    r = rec("comb.resolve", ts_ms, dur_ms)
    r["attrs"] = {"n": 6667, "outcome": outcome}
    if early is not None:
        r["attrs"]["early"] = early
    return r


def test_share_is_early_over_declined_spans_a_request_median(program):
    # request i leaves i of its 4 declines by the bound; a look-up that
    # ended another way is no decline whatever it says, nor is another
    # span's `early`; request 5 resolves nothing
    def one(i, t):
        if i == 5:
            return [rec("commit.collect", t + 1, 1)]
        other = rec("commit.match", t + 8, 1)
        other["attrs"] = {"outcome": "declined", "early": True}
        return [resolved(t + 1 + k, "declined", early=k < i)
                for k in range(4)] + [
            resolved(t + 6, "unknown", early=True),
            resolved(t + 7, "resident", early=False), other]
    run, records = run_of(6, one)
    program(records)
    # 0, 25, 50, 75, 100 % over five requests
    assert reader(METRIC).read(run) == pytest.approx(50.0)
    # a client's request: four batches that may build, four under the
    # floor that leave at once as `unknown`
    run, records = run_of(4, lambda i, t: [
        resolved(t + 0.5 + k, "declined" if k % 2 else "unknown",
                 early=bool(k % 2)) for k in range(8)])
    program(records)
    assert reader(METRIC).read(run) == 100.0
    # every decline paid the sort (its head repeated keys): 0 is a reading
    run, records = run_of(3, lambda i, t: [
        resolved(t + 1, "declined", early=False, dur_ms=7.5)])
    program(records)
    assert reader(METRIC).read(run) == 0.0
    assert reader("route.resolve_ms").read(run) == pytest.approx(7.5)


def test_reader_says_none_where_no_declined_span_carries_early(program):
    # the parent's program: `comb.resolve` with `n` and `outcome` alone
    run, records = run_of(8, lambda i, t: [
        resolved(t + 1, "declined", dur_ms=7.5)])
    program(records)
    assert reader("route.resolve_ms").read(run) == pytest.approx(7.5)
    assert reader(METRIC).read(run) is None
    # the cells whose tables are resident decline nothing
    run, records = run_of(8, lambda i, t: [
        resolved(t + 1, "resident", early=False),
        resolved(t + 3, "resident", early=False)])
    program(records)
    assert reader(METRIC).read(run) is None
    # too few requests that carry it, no record at all, an untraced run
    run, records = run_of(8, lambda i, t: [
        resolved(t + 1, "declined", early=True if i < 2 else None)])
    program(records)
    assert progspans.MIN_REQUESTS == 3
    assert reader(METRIC).read(run) is None
    program([])
    assert reader(METRIC).read(run) is None
    program([resolved(1, "declined", early=True)])
    assert reader(METRIC).read(
        {"spans": [], "requests": [{"wall_s": 0.01}] * 8}) is None


def test_the_runner_finds_the_reader_and_the_manifest_lists_it(program):
    import json
    import os

    from perfbench import run as runner

    read = runner.load_reader("layers", METRIC)
    run, records = run_of(3, lambda i, t: [
        resolved(t + 1, "declined", early=True)])
    program(records)
    assert read(run) == 100.0
    with pytest.raises(runner.Refused):
        runner.load_reader("layers", "route.no_such_metric")
    with open(os.path.join(runner.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == METRIC]
    assert entry == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_span", "layer": "route ladder",
        "moves": "request_ms.p50",
        "workloads": ["val100k-commit", "val10k-adjacent",
                      "val10k-skipping", "val10k-client"]}
