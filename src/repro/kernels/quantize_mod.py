"""Pallas kernel: modular (lattice) encode — Extension 3's hot path.

Layout: the flat parameter vector is reshaped to [n_blocks, BLOCK] (BLOCK
coords share one fp32 scale). Grid tiles rows; each program instance works on
a (TILE_ROWS, BLOCK) VMEM block — BLOCK is a multiple of 128 (lane dim) and
TILE_ROWS a multiple of 8 (sublane, fp32) so the VPU operates on full
registers. One HBM pass: read x, ref, u; write q and s.

Wire width follows the codec (quant/codecs.py): bits <= 8 writes uint8,
9..16 writes uint16, and ``pack4`` (bits <= 4) fuses the sub-byte bit-pack
into the same tile — the q output shrinks to [n_blocks, BLOCK/2] with two
codes per byte in the half-split nibble layout (low nibble = column c, high
nibble = column c + BLOCK/2; both halves are lane-aligned sub-blocks, so
the pack is two plain slices + shift/or, no strided lane access)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK = 256
DEFAULT_TILE_ROWS = 8


def _encode_kernel(x_ref, ref_ref, u_ref, q_ref, s_ref, *, safety: float,
                   min_scale: float, levels: int, pack4: bool):
    x = x_ref[...].astype(jnp.float32)
    r = ref_ref[...].astype(jnp.float32)
    u = u_ref[...]
    half = levels // 2
    dist = jnp.max(jnp.abs(x - r), axis=1, keepdims=True)      # [TR, 1]
    s = jnp.maximum(dist * (safety / half), min_scale)
    q = jnp.floor(x / s + u)                                   # stochastic round
    # codes are exact integers in [0, levels): the TPU has no direct
    # f32 -> u8/u16 cast and no sub-word shifts, so the codes (and the
    # nibble pack) live in int32 and narrow only at the store
    q = jnp.mod(q, levels).astype(jnp.int32)
    if pack4:
        # fused bit-pack: two 4-bit codes per byte (half-split layout)
        hcols = q.shape[1] // 2
        q = q[:, :hcols] | (q[:, hcols:] << 4)
    q_ref[...] = q.astype(q_ref.dtype)
    s_ref[...] = s


def quantize_mod_pallas(x, ref, u, *, safety: float = 8.0,
                        min_scale: float = 1e-8, bits: int = 8,
                        tile_rows: int = DEFAULT_TILE_ROWS,
                        interpret: bool = False, pack4: bool = False):
    """x, ref, u: [n_blocks, BLOCK] -> (q [n_blocks, BLOCK or BLOCK/2],
    s [n_blocks, 1]). q is uint8 (bits <= 8; BLOCK/2 wide when pack4) or
    uint16 (9..16 bits)."""
    n_rows, block = x.shape
    assert block % 128 == 0, f"BLOCK {block} must be a multiple of 128 (lanes)"
    assert n_rows % tile_rows == 0, (n_rows, tile_rows)
    assert bits <= 16, f"wire is uint8/uint16: bits={bits} unsupported"
    if pack4:
        assert bits <= 4, f"nibble packing needs bits <= 4, got {bits}"
        assert block % 256 == 0, \
            f"packed BLOCK/2 must stay a lane multiple: BLOCK={block}"
    q_cols = block // 2 if pack4 else block
    q_dtype = jnp.uint8 if bits <= 8 else jnp.uint16
    grid = (n_rows // tile_rows,)
    kern = functools.partial(_encode_kernel, safety=safety,
                             min_scale=min_scale, levels=1 << bits,
                             pack4=pack4)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_rows, block), lambda i: (i, 0)),
            pl.BlockSpec((tile_rows, block), lambda i: (i, 0)),
            pl.BlockSpec((tile_rows, block), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_rows, q_cols), lambda i: (i, 0)),
            pl.BlockSpec((tile_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_rows, q_cols), q_dtype),
            jax.ShapeDtypeStruct((n_rows, 1), jnp.float32),
        ],
        interpret=interpret,
        name="quantize_mod",
    )(x, ref, u)
