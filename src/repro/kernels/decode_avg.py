"""Pallas kernel: fused modular decode + gossip average (+ matched mask).

out = (y + decode(q, s; y)) / 2 in ONE pass over HBM (vs 4 passes unfused:
decode-read, decode-write, avg-read, avg-write). This is the receive side of
every SwarmSGD interaction — memory-bound, so fusion halves its HBM traffic.

The optional per-row `matched` mask fuses the "unmatched nodes keep their own
model" select into the same pass: the flat-buffer transport (core/bucket.py)
lays the swarm out as [n_nodes * rows_per_node, BLOCK] rows, so a node's
matched bit broadcasts to its row range and no separate jnp.where sweep over
the full model is needed (DESIGN.md §Perf).

``pack4`` fuses the sub-byte UNPACK into the same tile: q arrives packed
[R, BLOCK/2] (two 4-bit codes per byte, half-split layout — see
kernels/quantize_mod.py) and each nibble half decodes against its own
lane-aligned half of y, writing the two output halves separately so no
in-kernel concatenate is needed."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.quantize_mod import DEFAULT_TILE_ROWS


def _decode(q, s, y, *, levels: int, average: bool):
    qy = jnp.round(y / s)
    diff = jnp.mod(q - qy, levels)
    half = levels // 2
    wrapped = jnp.where(diff >= half, diff - levels, diff)
    x_hat = (qy + wrapped) * s
    return (y + x_hat) * 0.5 if average else x_hat


def _decode_avg_kernel(q_ref, s_ref, y_ref, o_ref, *, levels: int,
                       average: bool, pack4: bool, m_ref=None):
    s = s_ref[...]                                  # [TR, 1]
    y = y_ref[...].astype(jnp.float32)
    if pack4:
        # u8 -> f32 and sub-word shifts do not lower on the TPU: unpack
        # the nibbles in int32
        packed = q_ref[...].astype(jnp.int32)
        hcols = y.shape[1] // 2
        halves = []
        for lo_half, sl in ((True, slice(None, hcols)),
                            (False, slice(hcols, None))):
            nib = (packed & 0x0F) if lo_half else (packed >> 4) & 0x0F
            halves.append(_decode(nib.astype(jnp.float32), s, y[:, sl],
                                  levels=levels, average=average))
        if m_ref is not None:
            m = m_ref[...] != 0                     # [TR, 1]
            halves = [jnp.where(m, h, y[:, sl])
                      for h, sl in zip(halves, (slice(None, hcols),
                                                slice(hcols, None)))]
        o_ref[:, :hcols] = halves[0].astype(o_ref.dtype)
        o_ref[:, hcols:] = halves[1].astype(o_ref.dtype)
        return
    q = q_ref[...].astype(jnp.int32).astype(jnp.float32)
    out = _decode(q, s, y, levels=levels, average=average)
    if m_ref is not None:
        out = jnp.where(m_ref[...] != 0, out, y)    # m: [TR, 1] f32 mask
    o_ref[...] = out.astype(o_ref.dtype)


def decode_avg_pallas(q, s, y, *, bits: int = 8, average: bool = True,
                      matched=None, tile_rows: int = DEFAULT_TILE_ROWS,
                      interpret: bool = False, pack4: bool = False):
    """q:[R,B] uint8/uint16 (or [R,B/2] packed), s:[R,1] f32, y:[R,B]
    -> (y + x̂)/2 (or x̂ if not average).

    matched: optional [R] / [R,1] per-row mask; rows with mask==0 pass y
    through unchanged (fused — no extra HBM sweep).
    """
    n_rows, block = y.shape
    assert block % 128 == 0 and n_rows % tile_rows == 0
    q_cols = q.shape[1]
    assert q_cols == (block // 2 if pack4 else block), (q.shape, y.shape)
    grid = (n_rows // tile_rows,)
    in_specs = [
        pl.BlockSpec((tile_rows, q_cols), lambda i: (i, 0)),
        pl.BlockSpec((tile_rows, 1), lambda i: (i, 0)),
        pl.BlockSpec((tile_rows, block), lambda i: (i, 0)),
    ]
    kern = functools.partial(_decode_avg_kernel, levels=1 << bits,
                             average=average, pack4=pack4)
    if matched is None:
        args = (q, s, y)
    else:
        m = matched.reshape(n_rows, 1).astype(jnp.float32)
        in_specs.append(pl.BlockSpec((tile_rows, 1), lambda i: (i, 0)))

        def kern(q_ref, s_ref, y_ref, m_ref, o_ref, _k=1 << bits):  # noqa: F811
            _decode_avg_kernel(q_ref, s_ref, y_ref, o_ref, levels=_k,
                               average=average, pack4=pack4, m_ref=m_ref)
        args = (q, s, y, m)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tile_rows, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_rows, block), y.dtype),
        interpret=interpret,
        name="decode_avg",
    )(*args)
