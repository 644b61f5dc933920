"""idle_host.serve: share of the window in which the device is idle while
the innermost host span is the engine's host work or a garbage
collection (bench/spans.py)."""
from bench.spans import idle_host_share


def read(r):
    return idle_host_share(r.trace)
