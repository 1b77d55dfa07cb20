"""The reader of `entry.columns_ms` on hand-made spans: the sum of a
request's `commit.columns` spans, median over the requests that carry one;
None where the program records no such span (the parent's), under
progspans.MIN_REQUESTS and in an untraced run; the spans it lies inside
are not counted twice by `entry.collect_ms`; the runner finds the reader
by its file's name and the manifest lists it, wherever in the list."""
import pytest

from perfbench import progspans
from perfbench.tests.test_progspans import (  # noqa: F401  (fixture)
    program, reader, rec, run_of)

METRIC = "entry.columns_ms"


def columns(ts_ms, dur_ms, rows=100_000, fields="flag"):
    r = rec("commit.columns", ts_ms, dur_ms)
    r["attrs"] = {"rows": rows, "fields": fields}
    return r


def test_sum_of_the_requests_columns_spans_a_request_median(program):
    # a node's request: one read inside validate_basic, two inside the
    # collect; request i's reads take (i + 1) x (3 + 1 + 4) ms; request 5
    # checks no commit
    def one(i, t):
        if i == 5:
            return [rec("valset.hash", t + 1, 1)]
        k = i + 1
        # a span is its request's by where it STARTS
        return [rec("commit.validate_basic", t + 0.1, 3.5 * k),
                columns(t + 0.2, 3 * k, fields="flag,sig_len,addr_len"),
                rec("commit.collect", t + 4, 5.5 * k),
                columns(t + 4.1, 1 * k),
                columns(t + 4.2, 4 * k, fields="seconds,nanos,sig")]
    run, records = run_of(6, one)
    program(records)
    # 8, 16, 24, 32, 40 ms over the five requests that carry one
    assert reader(METRIC).read(run) == pytest.approx(24.0)
    # the spans they lie inside are read as before, none counted twice
    assert reader("entry.collect_ms").read(run) == pytest.approx(27.0)


def test_reader_says_none_where_the_program_has_no_such_span(program):
    # the parent's program: the same request, its rows walked in Python
    run, records = run_of(8, lambda i, t: [
        rec("commit.validate_basic", t + 0.1, 4),
        rec("commit.collect", t + 5, 4)])
    program(records)
    assert reader("entry.collect_ms").read(run) == pytest.approx(8.0)
    assert reader(METRIC).read(run) is None
    # too few requests that carry it, no record at all, an untraced run
    run, records = run_of(8, lambda i, t: [columns(t + 1, 2)] if i < 2
                          else [rec("commit.collect", t + 1, 2)])
    program(records)
    assert progspans.MIN_REQUESTS == 3
    assert reader(METRIC).read(run) is None
    program([])
    assert reader(METRIC).read(run) is None
    program([columns(1, 2)])
    assert reader(METRIC).read(
        {"spans": [], "requests": [{"wall_s": 0.01}] * 8}) is None


def test_the_runner_finds_the_reader_and_the_manifest_lists_it(program):
    import json
    import os

    from perfbench import run as runner

    read = runner.load_reader("layers", METRIC)
    run, records = run_of(3, lambda i, t: [columns(t + 1, 2.5)])
    program(records)
    assert read(run) == pytest.approx(2.5)
    with open(os.path.join(runner.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == METRIC]
    (collect,) = [m for m in manifest["per_layer"]
                  if m["name"] == "entry.collect_ms"]
    # the cells whose requests check a commit through the spans it lies in
    assert entry == dict(collect, name=METRIC)
    assert entry["workloads"] == [
        "val150-live", "val10k-adjacent", "val10k-skipping",
        "val10k-client", "val100k-commit", "val10k-mixed-commit"]
