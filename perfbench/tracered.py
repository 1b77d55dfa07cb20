"""From a profiler trace to numbers: the benchmark's own reduction, so every
PR computes device busy time, idle share and kernel time the same way.

A trace is read into plain data first (`read_xplane`), and everything else
here works on that plain data, so perfbench/tests can check the arithmetic
on a hand-made event list:

    planes = {plane name: {line name: [(event name, start_ns, dur_ns)]}}

What is read: on each `/device:TPU:<n>` plane the ONE line named
`XLA Ops` (the operations).  The `XLA Modules` and `Steps` lines hold the
same time again, one level up, and are never added in.  The host's spans
are the events whose name starts with `pb.` (the runner's
jax.profiler.TraceAnnotation around each request and each call into a
layer); they sit in the trace on the trace's own clock, which is what puts
a device gap and the host span open at that time side by side.
"""
from __future__ import annotations

import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "pb."
REQUEST_SPAN = "pb.request"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(path: str) -> dict:
    import jax

    planes = {}
    for pl in jax.profiler.ProfileData.from_file(path).planes:
        lines = planes.setdefault(pl.name, {})
        for ln in pl.lines:
            lines.setdefault(ln.name, []).extend(
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in ln.events)
    return planes


def inventory(planes: dict) -> dict:
    """{plane: {line: event count}} — printed before the result, so a
    reader sees which plane and line the numbers came from."""
    return {p: {ln: len(ev) for ln, ev in lines.items()}
            for p, lines in planes.items()}


def host_spans(planes: dict) -> list:
    """[(name, start_ns, end_ns)] of the runner's spans, by start."""
    out = []
    for pname, lines in planes.items():
        if pname.startswith("/device:"):
            continue
        for events in lines.values():
            out.extend((n, s, s + d) for n, s, d in events
                       if n.startswith(SPAN_PREFIX))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def union(intervals) -> list:
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def self_times(events) -> dict:
    """{event name: summed self time in ns} of one line's events: an
    event's duration less that of the events nested inside it, so that an
    enclosing operation (a `while`, a fusion's parent) does not count its
    body twice."""
    out = {}
    stack = []      # [name, end, self]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            out[name] = out.get(name, 0.0) + max(self_ns, 0.0)

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        close(s)
        if stack and s + d <= stack[-1][1]:
            stack[-1][2] -= d
        stack.append([name, s + d, d])
    close(float("inf"))
    return out


def innermost_segments(spans, lo: float, hi: float, outside: str) -> list:
    """[(name, start, end)] covering [lo, hi): at each instant the name of
    the innermost span open then, `outside` where none is.  Spans of one
    thread nest; a span that merely overlaps its predecessor is treated as
    its sibling."""
    edges = {lo, hi}
    for _, s, e in spans:
        edges.update(x for x in (s, e) if lo < x < hi)
    cuts = sorted(edges)
    out = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        name = outside
        best = None
        for n, s, e in spans:
            if s <= mid < e and (best is None or s >= best):
                name, best = n, s
        if out and out[-1][0] == name:
            out[-1] = (name, out[-1][1], b)
        else:
            out.append((name, a, b))
    return out


def reduce(planes: dict, bounds: str | None = None) -> dict:
    """The device's side of a traced sub-window: from the first traced
    request's start to the last one's end (the closed loop), or, with
    `bounds`, over the host span of that name (an open loop, whose
    requests overlap and whose profiler starts and stops between no two
    of them; no `request_busy_s` then).  Returns {} with "why" set when
    the trace has no TPU plane (a CPU rehearsal): device metrics are then
    absent, never made up."""
    dev = {p: lines[OPS_LINE] for p, lines in sorted(planes.items())
           if p.startswith(DEVICE_PLANE_PREFIX) and lines.get(OPS_LINE)}
    spans = host_spans(planes)
    requests = [(s, e) for n, s, e in spans if n == REQUEST_SPAN]
    if not dev:
        return {"why": f"the trace holds no {DEVICE_PLANE_PREFIX}* plane "
                       f"with an '{OPS_LINE}' line",
                "requests_traced": len(requests)}
    if bounds is not None:
        marks = [(s, e) for n, s, e in spans if n == bounds]
        if not marks:
            return {"why": f"the trace holds no {bounds} span",
                    "requests_traced": len(requests)}
        lo, hi = marks[0]
        spans = [sp for sp in spans if sp[0] != bounds]
        requests = [(s, e) for s, e in requests if lo <= s and e <= hi]
    elif not requests:
        return {"why": f"the trace holds no {REQUEST_SPAN} span",
                "requests_traced": 0}
    else:
        lo, hi = requests[0][0], requests[-1][1]
    busy_by_plane = {p: union(clip([(s, s + d) for _, s, d in ev], lo, hi))
                     for p, ev in dev.items()}
    used = {p: b for p, b in busy_by_plane.items() if b}
    if not used:
        return {"why": "no operation ran on the device between the first "
                       "and the last traced request",
                "requests_traced": len(requests)}
    busy_ns = sum(total(b) for b in used.values()) / len(used)
    # everything below reads the first chip that ran anything; with one
    # chip to a cell that is the chip
    first = sorted(used)[0]
    busy = used[first]
    ops = self_times(dev[first])
    gaps = []
    edge = lo
    for s, e in busy:
        if s > edge:
            gaps.append((edge, s))
        edge = e
    if hi > edge:
        gaps.append((edge, hi))
    leaf_spans = [sp for sp in spans if sp[2] > lo and sp[1] < hi]
    idle_by_span = {}
    g = 0       # gaps and segments are both in time order: one pass
    for name, s, e in innermost_segments(leaf_spans, lo, hi,
                                         "between requests"):
        while g < len(gaps) and gaps[g][1] <= s:
            g += 1
        k = g
        while k < len(gaps) and gaps[k][0] < e:
            idle = min(gaps[k][1], e) - max(gaps[k][0], s)
            if idle > 0:
                idle_by_span[name] = idle_by_span.get(name, 0.0) + idle
            k += 1

    def top(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    # the trace names an operation by its whole HLO line; its name is
    # what stands before " = "
    by_op = {}
    for name, ns in ops.items():
        short = name.split(" = ")[0].lstrip("%")[:80]
        by_op[short] = by_op.get(short, 0.0) + ns

    out = {
        "read": {"planes": sorted(used), "line": OPS_LINE,
                 "events": sum(len(dev[p]) for p in used)},
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9,
        "chips_busy": len(used),
        "requests_traced": len(requests),
        "request_busy_s": [total(clip(busy, s, e)) / 1e9
                           for s, e in requests],
        "device_ops": top(by_op),
        "idle_gaps": top(idle_by_span),
    }
    if bounds is not None:
        del out["request_busy_s"]
    return out
