"""sgd_update_roofline: share of its HBM roofline that the `sgd_update` Pallas
kernel reached in the window (bench/readers.py kernel_roofline)."""
from bench.readers import kernel_roofline


def read(r):
    return kernel_roofline(r, "sgd_update")
