"""jit'd public wrappers over the Pallas kernels.

`backend` selects pallas vs the pure-jnp ref:
  "pallas"     — real lowering (TPU target)
  "interpret"  — Pallas interpreter (CPU-correct; used by tests)
  "ref"        — pure-jnp oracle
None (the default) follows the platform at call time: "pallas" where
`jax.default_backend()` is the TPU, "ref" elsewhere (the TPU BlockSpecs
never lower on the CPU XLA backend). On a TPU the ref and interpreter
paths run only when a caller names them.
Arbitrary-shaped inputs are flattened and padded to the [rows, BLOCK] kernel
layout and un-padded on the way out.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref as ref_ops
from repro.kernels.decode_avg import decode_avg_pallas
from repro.kernels.quantize_mod import quantize_mod_pallas
from repro.kernels.sgd_update import sgd_update_pallas


def resolve_backend(backend: str | None) -> str:
    """The kernel backend for this call: an explicit choice wins, else the
    Pallas kernels on a TPU and the jnp oracle everywhere else."""
    if backend is not None:
        return backend
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _split_rows(x, block: int):
    """[..., k*block] -> [..., k, block], fenced so that XLA cannot fold it
    into a following merge of the leading dims. On a TPU the direct
    [n_nodes, n_padded] -> [rows, block] relayout of the flat gossip buffer
    compiles to code that grows with the buffer (minutes and ~1 GB of code
    at transformer-wmt width); split, then merged, it is one small copy
    and a free bitcast."""
    return jax.lax.optimization_barrier(
        x.reshape(x.shape[:-1] + (x.shape[-1] // block, block)))


def _to_blocks(x, block: int, tile_rows: int):
    if x.ndim > 1 and x.shape[-1] % block == 0:
        if x.shape[-1] != block:
            x = _split_rows(x, block)
        rows = x.reshape(-1, block)
        pad_rows = -rows.shape[0] % tile_rows
        if pad_rows:
            rows = jnp.pad(rows, ((0, pad_rows), (0, 0)))
        return rows, pad_rows * block
    flat = x.reshape(-1)
    n_rows = -(-flat.size // block)
    n_rows_pad = -(-n_rows // tile_rows) * tile_rows
    pad = n_rows_pad * block - flat.size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(n_rows_pad, block), pad


def _from_blocks(out, pad: int, like):
    """Inverse of `_to_blocks`: [rows, block] -> the shape of `like`."""
    block = out.shape[1]
    if like.ndim > 1 and like.shape[-1] % block == 0:
        out = out[:out.shape[0] - pad // block]
        if like.shape[-1] != block:
            out = jax.lax.optimization_barrier(out.reshape(
                like.shape[:-1] + (like.shape[-1] // block, block)))
        return out.reshape(like.shape)
    flat = out.reshape(-1)
    if pad:
        flat = flat[:-pad]
    return flat.reshape(like.shape)


def quantize_mod(x, ref, u, *, block: int = 256, safety: float = 8.0,
                 min_scale: float = 1e-8, bits: int = 8,
                 backend: str | None = None, tile_rows: int = 8,
                 pack4: bool = False):
    """pack4 (bits <= 4): q ships packed [R, block/2], two codes per byte
    (half-split nibble layout; fused into the encode tile)."""
    backend = resolve_backend(backend)
    xb, pad = _to_blocks(x, block, tile_rows)
    rb, _ = _to_blocks(ref, block, tile_rows)
    ub, _ = _to_blocks(u, block, tile_rows)
    if backend == "ref":
        q, s = ref_ops.quantize_mod_ref(xb, rb, ub, safety=safety,
                                        min_scale=min_scale, bits=bits,
                                        pack4=pack4)
    else:
        q, s = quantize_mod_pallas(xb, rb, ub, safety=safety,
                                   min_scale=min_scale, bits=bits,
                                   tile_rows=tile_rows,
                                   interpret=(backend == "interpret"),
                                   pack4=pack4)
    return q, s, pad


def decode_avg(q, s, y, *, block: int = 256, bits: int = 8,
               average: bool = True, matched=None,
               backend: str | None = None, tile_rows: int = 8,
               pack4: bool = False):
    """q,s from quantize_mod; y: the receiver tensor (original shape).

    matched: optional per-row [R] mask (R = q.shape[0]); rows with mask==0
    return y unchanged — the gossip "unmatched keeps own model" select, fused
    into the decode+average pass. pack4: q arrives packed [R, block/2]; the
    unpack is fused into the decode tile.
    """
    backend = resolve_backend(backend)
    yb, pad = _to_blocks(y, block, tile_rows)
    if backend == "ref":
        out = ref_ops.decode_avg_ref(q, s, yb, bits=bits, average=average,
                                     matched=matched, pack4=pack4)
    else:
        out = decode_avg_pallas(q, s, yb, bits=bits, average=average,
                                matched=matched, tile_rows=tile_rows,
                                interpret=(backend == "interpret"),
                                pack4=pack4)
    return _from_blocks(out, pad, y)


def sgd_fused_update(p, g, m, *, lr, mu: float = 0.9, wd: float = 0.0,
                     nesterov: bool = False, block: int = 512,
                     backend: str | None = None, tile_rows: int = 8):
    """Fused momentum/weight-decay SGD update — THE optimizer hot path
    (optim/sgd.py routes every momentum update here on the packed flat
    buffer). `lr` may be traced (the engines pass lr_fn(state.step)): the
    Pallas path ships it as an SMEM scalar, the ref path is plain jnp."""
    backend = resolve_backend(backend)
    pb, pad = _to_blocks(p, block, tile_rows)
    gb, _ = _to_blocks(g, block, tile_rows)
    mb, _ = _to_blocks(m, block, tile_rows)
    if backend == "ref":
        pn, mn = ref_ops.sgd_update_ref(pb, gb, mb, lr=lr, mu=mu, wd=wd,
                                        nesterov=nesterov)
    else:
        pn, mn = sgd_update_pallas(pb, gb, mb, lr=lr, mu=mu, wd=wd,
                                   nesterov=nesterov, tile_rows=tile_rows,
                                   interpret=(backend == "interpret"))
    return _from_blocks(pn, pad, p), _from_blocks(mn, pad, m)
