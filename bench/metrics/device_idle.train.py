"""Share of the window in which no operation ran on the device, from the
profiler trace (1 - busy union / window), averaged over the chips."""


def read(r):
    return 100.0 * r.trace.idle_share
