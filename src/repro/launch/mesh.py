"""Mesh builders. FUNCTIONS only — importing this module never touches jax
device state (device count is locked at first jax init, and the dry-run
must set XLA_FLAGS before that)."""
from __future__ import annotations

import jax


def auto_mesh(shape, axes, devices=None):
    """`jax.make_mesh` with GSPMD-propagated (Auto) axes. jax >= 0.7
    defaults mesh axes to Explicit sharding-in-types, which the specs in
    this repo (launch/specs.py, the node-stacked swarm state) do not use."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def node_mesh(n_nodes: int, devices=None):
    """The swarm's ("node",) mesh over `devices` (default: every device the
    process sees), one node per device — or None on a single device, where
    the nodes are vmapped on that one chip. More than one device with
    n_nodes != device count raises: a node never spans or shares chips."""
    devices = list(jax.devices() if devices is None else devices)
    if len(devices) == 1:
        return None
    if n_nodes != len(devices):
        raise ValueError(
            f"{len(devices)} devices carry one swarm node each, but "
            f"n_nodes={n_nodes}: run --nodes {len(devices)} (or restrict "
            "the process to one device to vmap the nodes on it)")
    return auto_mesh((len(devices),), ("node",), devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Target: TPU v5e. Single pod = 16x16 (256 chips), multi-pod = 2 pods.

    Axes: ("pod",) "data", "model". SwarmSGD nodes live on the node axes
    (see repro.launch.specs.node_axes_for): default ("pod","data") -> 32
    gossip nodes x 16-way tensor parallel.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


# TPU v5e hardware constants (per chip) — inputs of the roofline analysis
# and the wall-clock cost model (sched/cost.py) only; no measured number is
# ever reported as a share of them.
PEAK_FLOPS_BF16 = 197e12     # FLOP/s
HBM_BW = 819e9               # B/s
ICI_LINK_BW = 50e9           # B/s per link (conservative single-link figure)
DCN_LINK_BW = 6.25e9         # B/s cross-pod data-center link (~50 Gb/s per
# host NIC) — the slow tier of hierarchical gossip pricing (sched/cost.py;
# DESIGN.md §Hierarchy): intra-group payloads ride ICI, inter-group DCN
HBM_PER_CHIP = 16 * 1024**3  # 16 GiB
