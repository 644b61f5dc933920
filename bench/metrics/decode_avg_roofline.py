"""decode_avg_roofline: share of its HBM roofline that the `decode_avg` Pallas
kernel reached in the window (bench/readers.py kernel_roofline)."""
from bench.readers import kernel_roofline


def read(r):
    return kernel_roofline(r, "decode_avg")
