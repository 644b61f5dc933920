"""Compiled multi-superstep driver (DESIGN.md §Fusion).

The per-step driver dispatches one jitted superstep per Python-loop
iteration — at production scale the host-side dispatch (pytree flatten,
argument processing, one XLA call per superstep) dominates the simulated
per-interaction cost the paper's time-to-accuracy claim rests on (ROADMAP
item 5). This module folds K supersteps into ONE dispatch: a `lax.scan`
whose xs are the stacked scheduler inputs (perm/h/mask rows straight from
`sched.bridge.stacked_engine_inputs`, or the presampled matching/h streams
of `launch.train.presample_inputs`) plus the prefetched batch stack, and
whose carry is the SwarmState and the rng key.

Bitwise contract: the body performs `key, sub = jax.random.split(key)`
then `step_fn(state, batch_t, perm_t, h_t, sub[, mask_t])` — exactly the
per-step driver's host loop, with the split traced instead of eager
(threefry is deterministic either way). A chunked run is therefore
bitwise identical to the per-step driver given the same initial state and
key, for every (mode × transport × codec) the engine supports
(tests/test_scan_driver.py), and chunk boundaries are exact checkpoint
points: (state, key) returned at a boundary resume the trajectory
bit-exactly.

Donation: the chunk jit donates (state, key) — params/opt/prev/residual/
inflight update in place across the boundary instead of double-buffering
the packed model. Callers MUST rebind both from the return value; the
donated inputs are dead after the call (tests/test_scan_driver.py asserts
the aliasing actually happens in the lowered module).

Composition with compress_state (DESIGN.md §Hierarchy): when the comm
copy lives codec-encoded, `state.prev` is a tuple of wire-word arrays —
still ordinary carry leaves, so they donate through the scan boundary
like any other buffer and the chunked run stays bitwise the per-step
driver's (tests/test_hier.py). Hierarchical perm streams are plain [K, n]
xs rows; the scan body never learns which tier a row came from.
"""
from __future__ import annotations

import jax


def make_superstep_scan(step_fn, *, with_mask: bool = False,
                        donate: bool = True):
    """Wrap a per-superstep engine step into a jitted K-superstep chunk.

    step_fn: superstep(state, batch, perm, h, rng[, mask]) -> (state,
    metrics) — any algorithm step from make_swarm_step / make_algorithm
    (jitted or not: a jitted fn inlines into the scan trace).

    Returns chunk(state, key, batch, perm, h[, mask]) -> (state, key,
    metrics): batch leaves, perm, h (and mask when with_mask) carry a
    leading [K] scan dim; metrics leaves come back stacked [K]. K is a
    trace-time constant — a different chunk length (e.g. the last partial
    chunk) compiles once per length.

    state and key are DONATED by default; pass donate=False when the
    caller still needs the pre-chunk buffers (A/B comparisons, tests).
    """

    def body(carry, xs):
        st, k = carry
        k, sub = jax.random.split(k)
        if with_mask:
            batch, perm, h, mask = xs
            st, metrics = step_fn(st, batch, perm, h, sub, mask)
        else:
            batch, perm, h = xs
            st, metrics = step_fn(st, batch, perm, h, sub)
        return (st, k), metrics

    if with_mask:
        def chunk(state, key, batch, perm, h, mask):
            (state, key), ms = jax.lax.scan(body, (state, key),
                                            (batch, perm, h, mask))
            return state, key, ms
    else:
        def chunk(state, key, batch, perm, h):
            (state, key), ms = jax.lax.scan(body, (state, key),
                                            (batch, perm, h))
            return state, key, ms

    return jax.jit(chunk, donate_argnums=(0, 1) if donate else ())
