"""collective_ms.train: device time of the gossip's collective permutes
per chip and superstep (bench/readers.py)."""
from bench.readers import collective_ms_per_superstep


def read(r):
    return collective_ms_per_superstep(r)
