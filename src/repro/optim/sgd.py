"""SGD with (Nesterov) momentum and decoupled weight decay.

This is the optimizer of the paper's experiments (momentum SGD with the
sequential baseline's schedule, §5). The fused param/momentum update is a
memory-bound hot-spot: the momentum path packs the whole model into ONE
flat fp32 buffer (core/bucket.py pack_rows — same wire layout as the
gossip buffer, as kernel rows) and runs a single `kernels.sgd_fused_update`
sweep — the Pallas kernel on a TPU, the pure-jnp ref elsewhere
(kernels/ops.py). The ref sweep replicates the historical per-leaf tree-map
update op-for-op, so the fused path is bitwise identical to it (asserted
in tests/test_kernels.py); `fused=False` keeps the per-leaf path as the
oracle.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class SGDConfig:
    lr: float = 0.1
    momentum: float = 0.9
    nesterov: bool = False
    weight_decay: float = 0.0
    state_dtype: str = "float32"
    fused: bool = True       # flat-buffer kernel path for the momentum
    # update (bitwise = the per-leaf path); momentum=0 always runs per-leaf


def sgd_init(cfg: SGDConfig, params):
    dt = jnp.dtype(cfg.state_dtype)
    if cfg.momentum == 0.0:
        return {}
    return {"m": jax.tree.map(lambda p: jnp.zeros(p.shape, dt), params)}


def _sgd_update_fused(cfg: SGDConfig, params, grads, state, lr):
    """One kernel sweep over the packed model: params/grads/momentum each
    pack to [n_padded // block, block] fp32 rows (zero padding is a fixed
    point of the update: m'=0, p'=0), update once, unpack with the original
    leaf dtypes — exactly the per-leaf `upd` computation on a different layout."""
    from repro.core import bucket as B
    from repro.kernels import sgd_fused_update
    p_layout = B.build_flat_layout(params)
    m_layout = B.build_flat_layout(state["m"])
    pbuf = B.pack_rows(p_layout, params)
    gbuf = B.pack_rows(p_layout, grads)
    mbuf = B.pack_rows(m_layout, state["m"])
    pn, mn = sgd_fused_update(pbuf, gbuf, mbuf, lr=lr, mu=cfg.momentum,
                              wd=cfg.weight_decay, nesterov=cfg.nesterov,
                              block=p_layout.block)
    return B.unpack_rows(p_layout, pn), {"m": B.unpack_rows(m_layout, mn)}


def sgd_update(cfg: SGDConfig, params, grads, state, lr=None):
    lr = cfg.lr if lr is None else lr
    if state and cfg.fused:
        return _sgd_update_fused(cfg, params, grads, state, lr)

    def upd(p, g, m):
        g = g.astype(jnp.float32)
        if cfg.weight_decay:
            g = g + cfg.weight_decay * p.astype(jnp.float32)
        if m is None:
            step = g
            new_m = None
        else:
            new_m = cfg.momentum * m.astype(jnp.float32) + g
            step = g + cfg.momentum * new_m if cfg.nesterov else new_m
        new_p = (p.astype(jnp.float32) - lr * step).astype(p.dtype)
        return new_p, new_m

    if not state:
        new = jax.tree.map(lambda p, g: upd(p, g, None)[0], params, grads)
        return new, {}
    flat_p, tdef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(state["m"])
    outs = [upd(p, g, m) for p, g, m in zip(flat_p, flat_g, flat_m)]
    new_p = jax.tree.unflatten(tdef, [o[0] for o in outs])
    dt = jnp.dtype(cfg.state_dtype)
    new_m = jax.tree.unflatten(tdef, [o[1].astype(dt) for o in outs])
    return new_p, {"m": new_m}
