"""Requests completed in the window over the window's length: all the work
and all the time, from the first request's start to the last one's return.
In an open window: the requests scheduled over its length, the offered
rate, which no change can move; its latencies and `failed` judge it."""


def read(run):
    return len(run["requests"]) / run["window_s"] if run["requests"] else None
