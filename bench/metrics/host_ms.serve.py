"""host_ms.serve: host milliseconds per engine step, from the program's
own spans: admission, the preps, the dispatches, the harvests and the
retire (bench/spans.py HOST_WORK) that start in the window, over the
steps (one `serve.admit` each)."""
from bench.spans import host_ms_per_step


def read(r):
    return host_ms_per_step(r.trace)
