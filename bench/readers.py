"""Reductions the per-layer metric readers share."""
from __future__ import annotations

from typing import Optional

from bench import counts


def kernel_roofline(r, kernel: str) -> Optional[float]:
    """Share of its roofline that a Pallas kernel reached in the window:
    the bytes of its operands and results (from the shapes in each call's
    compiled instruction) at the HBM peak, over its summed device time.
    These kernels do a few operations per element, far under the FLOP
    per byte the chip needs to be compute-bound, so bytes bound them."""
    evs = r.trace.ops(kernel)
    if not evs:
        return None
    return counts.roofline_share([counts.hlo_bytes(e.name) for e in evs],
                                 [0.0], [e.dur_ns * 1e-9 for e in evs],
                                 r.peaks)


def program_ms(r, module: str) -> Optional[float]:
    """Mean device time of one dispatch of a jitted program, in ms."""
    evs = r.trace.ops(module, line="modules")
    if not evs:
        return None
    return 1e3 * sum(e.dur_ns for e in evs) * 1e-9 / len(evs)


def collective_ms_per_superstep(r) -> Optional[float]:
    """Device time of the collective permutes, start to done, per chip and
    per superstep of the window, in ms. The start-to-done spans are on the
    async line, which the profiler writes for some chips only (one of four
    on a v5e host); where no chip has one, the synchronous ops stand in.
    Averaged over the chips that hold such events."""
    for pattern, line in ((r"collective-permute(-start)?", "async_ops"),
                          (r"collective-permute(-done)?", "ops")):
        chips = [evs for evs in r.trace.ops_by_device(pattern, line) if evs]
        if chips:
            ns = sum(e.dur_ns for evs in chips for e in evs) / len(chips)
            return 1e3 * ns * 1e-9 / r.job["readings"]["supersteps"]
    return None
