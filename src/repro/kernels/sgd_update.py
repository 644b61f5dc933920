"""Pallas kernel: fused SGD(momentum, weight-decay) parameter update.

p' = p - lr * (m' [+ mu*m' if nesterov]),  m' = mu*m + (g + wd*p)

The optimizer update is memory-bound (3 reads + 2 writes, ~zero flops/byte);
fusing it into one kernel is the standard trick to avoid XLA materializing
intermediates between the momentum update and the parameter write. This is
the optimizer hot path: `optim/sgd.py` routes the momentum update through
`kernels/ops.py::sgd_fused_update` on the packed flat buffer
(core/bucket.py pack_rows), with the pure-jnp ref as the CPU fallback.

`lr` is a TRACED scalar — the engines drive it from `lr_fn(state.step)`
inside jit — so it ships as a (1,) f32 SMEM operand rather than a static
kernel parameter; mu/wd/nesterov are config constants and stay baked in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_COLS = 512
DEFAULT_TILE_ROWS = 8


def _sgd_kernel(lr_ref, p_ref, g_ref, m_ref, p_out, m_out, *, mu: float,
                wd: float, nesterov: bool):
    lr = lr_ref[0]
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    if wd:
        g = g + wd * p
    m_new = mu * m + g
    step = g + mu * m_new if nesterov else m_new
    p_out[...] = (p - lr * step).astype(p_out.dtype)
    m_out[...] = m_new.astype(m_out.dtype)


def sgd_update_pallas(p, g, m, *, lr, mu: float = 0.9, wd: float = 0.0,
                      nesterov: bool = False,
                      tile_rows: int = DEFAULT_TILE_ROWS,
                      interpret: bool = False):
    """p, g, m: [R, C] (C multiple of 128) -> (p_new, m_new).

    lr may be a python float or a traced 0-d array (SMEM scalar operand)."""
    n_rows, cols = p.shape
    assert cols % 128 == 0 and n_rows % tile_rows == 0
    grid = (n_rows // tile_rows,)
    lr_arr = jnp.asarray(lr, jnp.float32).reshape((1,))
    kern = functools.partial(_sgd_kernel, mu=float(mu), wd=float(wd),
                             nesterov=nesterov)
    spec = pl.BlockSpec((tile_rows, cols), lambda i: (i, 0))
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), spec, spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((n_rows, cols), p.dtype),
                   jax.ShapeDtypeStruct((n_rows, cols), m.dtype)],
        interpret=interpret,
        name="sgd_update",
    )(lr_arr, p, g, m)
