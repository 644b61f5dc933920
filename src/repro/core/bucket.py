"""Bucketed flat-buffer gossip transport (DESIGN.md §Perf).

The unit of exchange in SwarmSGD is a *whole model*, not a parameter tensor:
each matched pair swaps one payload per interaction. The per-leaf transports
in ``core/swarm.py`` historically issued one collective (and, quantized, one
encode/decode sweep) per pytree leaf — dozens of small collectives for a
transformer. This module packs the node-stacked param pytree into ONE padded
``[n_nodes, n_padded]`` fp32 buffer so gossip becomes a single collective
over a single contiguous payload, and the quantized path runs through the
Pallas kernel wrappers in ``kernels/ops.py`` (``quantize_mod`` encode,
``decode_avg`` fused decode + average + matched-mask).

Wire format (see DESIGN.md §Perf for the full layout):

* leaves are flattened per node and concatenated in pytree-leaf order;
* each leaf segment is zero-padded up to a multiple of ``block`` (the quant
  scale-block size) so no scale block straddles two tensors;
* the total per-node width is padded up to ``block * tile_rows`` so the
  buffer maps onto the ``[rows, block]`` Pallas kernel layout with zero
  re-padding — ``rows_per_node = n_padded // block`` is a multiple of the
  kernel's sublane tile;
* exact mode ships the fp32 buffer; quantized mode ships the
  ``(uint8 q [rows, block], fp32 scales [rows, 1])`` pair.

Layouts are cached per (tree structure, shapes, dtypes, block) — the
flatten plan is computed once per model, not once per superstep.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.quant.codecs import LatticeCodec, WireCodec, make_codec
from repro.quant.schemes import ModularQuantConfig, payload_bytes

DEFAULT_BLOCK = 256      # coords per quant scale block (lane-dim multiple)
DEFAULT_TILE_ROWS = 8    # kernel sublane tile: rows_per_node must divide


def as_codec(quant_or_codec) -> Optional[WireCodec]:
    """Normalize the transport's wire parameter: a WireCodec passes
    through, a ModularQuantConfig wraps into the lattice codec (the
    pre-codec behavior), None stays None (exact fp32)."""
    if quant_or_codec is None or isinstance(quant_or_codec, WireCodec):
        return quant_or_codec
    assert isinstance(quant_or_codec, ModularQuantConfig), quant_or_codec
    return LatticeCodec(quant_or_codec)


@dataclass(frozen=True)
class BucketLayout:
    """Precomputed flatten plan for one node-stacked pytree structure."""
    treedef: Any
    n_nodes: int
    shapes: Tuple[Tuple[int, ...], ...]   # per-leaf shape, node dim stripped
    dtypes: Tuple[Any, ...]
    offsets: Tuple[int, ...]              # leaf start col in the buffer
    sizes: Tuple[int, ...]                # true coords per leaf per node
    seg_sizes: Tuple[int, ...]            # block-aligned segment widths
    n_coords: int                         # sum(sizes): true coords per node
    n_padded: int                         # buffer width incl. all padding
    block: int
    tile_rows: int

    @property
    def rows_per_node(self) -> int:
        return self.n_padded // self.block

    def payload_num_bytes(self, quant=None) -> int:
        """Exact wire bytes PER NODE for one gossip send of this buffer.
        `quant` is None (fp32), a ModularQuantConfig (lattice codec — the
        pre-codec spelling) or any WireCodec; the codec's declared
        WireLayout is the single pricing source (quant/codecs.py)."""
        if quant is None:
            return 4 * self.n_padded
        codec = as_codec(quant)
        assert codec.block == self.block, (codec.block, self.block)
        n = codec.payload_num_bytes(self.n_padded)
        if isinstance(quant, ModularQuantConfig) and not codec.packed:
            # the historical closed-form formula must agree with the layout
            assert n == payload_bytes(quant, self.n_padded), (n, quant)
        return n


_LAYOUT_CACHE: dict = {}


def build_layout(tree, *, block: int = DEFAULT_BLOCK,
                 tile_rows: int = DEFAULT_TILE_ROWS) -> BucketLayout:
    """Flatten plan for a node-stacked tree (cached per structure)."""
    leaves, treedef = jax.tree.flatten(tree)
    assert leaves, "cannot build a bucket layout for an empty tree"
    n_nodes = leaves[0].shape[0]
    shapes = tuple(tuple(x.shape[1:]) for x in leaves)
    dtypes = tuple(jnp.dtype(x.dtype) for x in leaves)
    key = (treedef, n_nodes, shapes, dtypes, block, tile_rows)
    hit = _LAYOUT_CACHE.get(key)
    if hit is not None:
        return hit
    offsets, sizes, seg_sizes = [], [], []
    off = 0
    for shp in shapes:
        size = int(np.prod(shp, dtype=np.int64)) if shp else 1
        seg = -(-size // block) * block
        offsets.append(off)
        sizes.append(size)
        seg_sizes.append(seg)
        off += seg
    total_align = block * tile_rows
    n_padded = -(-off // total_align) * total_align
    layout = BucketLayout(treedef, n_nodes, shapes, dtypes, tuple(offsets),
                          tuple(sizes), tuple(seg_sizes), sum(sizes),
                          n_padded, block, tile_rows)
    _LAYOUT_CACHE[key] = layout
    return layout


_FLAT_LAYOUT_CACHE: dict = {}


def build_flat_layout(tree, *, block: int = DEFAULT_BLOCK,
                      tile_rows: int = DEFAULT_TILE_ROWS) -> BucketLayout:
    """Flatten plan for a SINGLE-node (un-stacked) pytree: the same wire
    layout as `build_layout` but leaves keep their full shape (no leading
    node dim to strip). Used by the fused optimizer path (optim/sgd.py):
    inside the vmapped local-step loop each node's param/momentum trees
    pack to ONE [n_padded] fp32 vector so the whole model updates in a
    single `kernels.sgd_fused_update` sweep. Returns a BucketLayout with
    n_nodes == 1; use `pack_flat`/`unpack_flat` (not pack/unpack)."""
    leaves, treedef = jax.tree.flatten(tree)
    assert leaves, "cannot build a flat layout for an empty tree"
    shapes = tuple(tuple(x.shape) for x in leaves)
    dtypes = tuple(jnp.dtype(x.dtype) for x in leaves)
    key = (treedef, shapes, dtypes, block, tile_rows)
    hit = _FLAT_LAYOUT_CACHE.get(key)
    if hit is not None:
        return hit
    offsets, sizes, seg_sizes = [], [], []
    off = 0
    for shp in shapes:
        size = int(np.prod(shp, dtype=np.int64)) if shp else 1
        seg = -(-size // block) * block
        offsets.append(off)
        sizes.append(size)
        seg_sizes.append(seg)
        off += seg
    total_align = block * tile_rows
    n_padded = -(-off // total_align) * total_align
    layout = BucketLayout(treedef, 1, shapes, dtypes, tuple(offsets),
                          tuple(sizes), tuple(seg_sizes), sum(sizes),
                          n_padded, block, tile_rows)
    _FLAT_LAYOUT_CACHE[key] = layout
    return layout


def _leaf_rows(x, seg: int, block: int):
    """One leaf as its zero-padded [seg // block, block] fp32 rows."""
    if x.size == seg and x.ndim and x.shape[-1] % block == 0:
        return x.reshape(-1, block).astype(jnp.float32)
    flat = jnp.pad(x.reshape(-1), (0, seg - x.size))
    return flat.reshape(-1, block).astype(jnp.float32)


def pack_rows(layout: BucketLayout, tree) -> jax.Array:
    """Un-stacked pytree -> [n_padded // block, block] fp32 rows: the flat
    layout as the kernels tile it (zeros-prefill + one row-slice write per
    leaf). Built row-wise, never through a 1-D vector: on a TPU the
    relayout from a leaf to a 1-D vector and on to kernel rows compiles to
    code that grows with the model (over a minute of every transformer-wmt
    superstep's compile)."""
    buf = jnp.zeros((layout.n_padded // layout.block, layout.block),
                    jnp.float32)
    for x, off, seg in zip(jax.tree.leaves(tree), layout.offsets,
                           layout.seg_sizes):
        buf = jax.lax.dynamic_update_slice_in_dim(
            buf, _leaf_rows(x, seg, layout.block), off // layout.block, 0)
    return buf


def unpack_rows(layout: BucketLayout, buf: jax.Array):
    """[n_padded // block, block] rows -> un-stacked pytree (original
    dtypes); the inverse of `pack_rows`."""
    b = layout.block
    outs = []
    for off, size, seg, shp, dt in zip(layout.offsets, layout.sizes,
                                       layout.seg_sizes, layout.shapes,
                                       layout.dtypes):
        rows = jax.lax.slice_in_dim(buf, off // b, (off + seg) // b, axis=0)
        if size == seg and shp and shp[-1] % b == 0:
            leaf = rows.reshape(shp)
        else:
            leaf = rows.reshape(-1)[:size].reshape(shp)
        outs.append(leaf.astype(dt))
    return jax.tree.unflatten(layout.treedef, outs)


def pack_flat(layout: BucketLayout, tree) -> jax.Array:
    """Un-stacked pytree -> [n_padded] fp32 vector."""
    return pack_rows(layout, tree).reshape(-1)


def unpack_flat(layout: BucketLayout, buf: jax.Array):
    """[n_padded] fp32 vector -> un-stacked pytree (original dtypes)."""
    return unpack_rows(layout, buf.reshape(-1, layout.block))


def pack(layout: BucketLayout, tree) -> jax.Array:
    """Node-stacked pytree -> [n_nodes, n_padded] fp32 flat buffer.

    Zeros-prefill + per-leaf slice writes: the zero prefill provides all the
    alignment padding for free, and each leaf is copied exactly once
    (XLA CPU's concatenate would add a full extra pass per operand)."""
    leaves = jax.tree.leaves(tree)
    buf = jnp.zeros((layout.n_nodes, layout.n_padded), jnp.float32)
    for x, off, size in zip(leaves, layout.offsets, layout.sizes):
        buf = buf.at[:, off:off + size].set(
            x.reshape(layout.n_nodes, size).astype(jnp.float32))
    return buf


def unpack(layout: BucketLayout, buf: jax.Array):
    """[n_nodes, n_padded] flat buffer -> node-stacked pytree (orig dtypes)."""
    outs = []
    for off, size, shp, dt in zip(layout.offsets, layout.sizes,
                                  layout.shapes, layout.dtypes):
        seg = jax.lax.slice_in_dim(buf, off, off + size, axis=1)
        outs.append(seg.astype(dt).reshape((layout.n_nodes,) + shp))
    return jax.tree.unflatten(layout.treedef, outs)


# ---------------------------------------------------------------------------
# Flat-buffer gossip primitives (the whole swarm = one payload tensor)
# ---------------------------------------------------------------------------


def row_mask(matched, rows_per_node: int):
    """Per-node bool [n_nodes] -> the kernels' per-row mask column
    [n_nodes * rows_per_node, 1], as a lookup of each row's node. (As
    `jnp.repeat` it is a broadcast plus a reshape, which the TPU compiler
    turns into a relayout whose code grows with the model.)"""
    node_of_row = jnp.arange(matched.shape[0] * rows_per_node) // rows_per_node
    return matched[node_of_row][:, None]


def gossip_flat_exact(buf, perm, matched=None):
    """(buf + buf[perm]) / 2 — ONE row permute over one tensor. With
    `matched=None` no mask pass is needed: `perm` is an involution with
    fixed points at unmatched nodes, and (x + x) * 0.5 == x bitwise for
    every finite float. A non-None `matched` (bool [n_nodes]) additionally
    gates the landing — the scheduler bridge uses this to run PARTIAL
    matchings whose perm entries may pair nodes that did not interact this
    bin (pool/static-matching transports; sched/bridge.py). For a full
    mask the `where` selects bitwise-identical values, so the masked path
    reproduces the unmasked trajectory exactly."""
    avg = (buf + permute_rows(buf, perm, buf.shape[0])) * 0.5
    if matched is None:
        return avg
    return jnp.where(matched[:, None], avg, buf)


def encode_flat(qcfg: ModularQuantConfig, buf, prev_buf, rng, *,
                tile_rows: int = DEFAULT_TILE_ROWS, backend=None):
    """Encode the whole flat buffer: ONE quantize_mod kernel sweep.

    -> (q [n_nodes*rows_per_node, block or block/2] uint8/uint16, s fp32
    [same rows, 1]). Scales are per block; prev_buf is the sender-local
    distance proxy. Thin wrapper over the lattice WireCodec — bits <= 16
    all run flat now (uint16 wire; sub-byte widths ship packed)."""
    return as_codec(qcfg).encode(buf, prev_buf, rng, tile_rows=tile_rows,
                                 backend=backend)


def gossip_flat_coded(codec: WireCodec, buf, prev_buf, perm, matched, rng, *,
                      residual=None, tile_rows: int = DEFAULT_TILE_ROWS,
                      backend=None):
    """Codec-parametric flat gossip: encode once (ONE kernel sweep),
    permute every wire-group tensor, decode+average+mask in one fused
    sweep. Returns (mixed, new_residual); new_residual is None unless the
    codec carries an error-feedback slot, in which case the update is
    gated by `matched` — an unconsumed payload leaves the residual (and
    the un-refreshed comm copy) to re-enter the next encode."""
    n_nodes, n_padded = buf.shape
    rpn = n_padded // codec.block
    new_residual = None
    if codec.carries_residual:
        wire, res_after = codec.encode_ef(buf, prev_buf, rng, residual,
                                          tile_rows=tile_rows,
                                          backend=backend)
        new_residual = jnp.where(matched[:, None], res_after,
                                 residual if residual is not None
                                 else jnp.zeros_like(buf))
    else:
        wire = codec.encode(buf, prev_buf, rng, tile_rows=tile_rows,
                            backend=backend)
    wire_p = tuple(permute_rows(w, perm, n_nodes) for w in wire)
    m_rows = row_mask(matched, rpn)
    out = codec.decode_avg(wire_p, buf, m_rows, tile_rows=tile_rows,
                           backend=backend)
    return out, new_residual


def gossip_flat_quantized(qcfg, buf, prev_buf, perm, matched, rng, *,
                          tile_rows: int = DEFAULT_TILE_ROWS, backend=None):
    """Quantized flat gossip (lattice codec, pre-codec entry point):
    encode once, permute the (q, s) payload pair, decode+average+mask in
    one fused decode_avg sweep."""
    out, _ = gossip_flat_coded(as_codec(qcfg), buf, prev_buf, perm, matched,
                               rng, tile_rows=tile_rows, backend=backend)
    return out


def gossip_flat_mean(buf, mask=None):
    """(Masked) global mean over the node axis, broadcast back — the flat
    form of LocalSGD's resync / AllReduce's gradient averaging. With `mask`
    the mean runs over PARTICIPANTS only and is still broadcast everywhere
    (server-broadcast semantics under the scheduler bridge)."""
    if mask is None:
        mu = jnp.mean(buf, axis=0, keepdims=True)
    else:
        w = mask.astype(jnp.float32)
        mu = jnp.sum(w[:, None] * buf, axis=0, keepdims=True) / \
            jnp.maximum(jnp.sum(w), 1.0)
    return jnp.broadcast_to(mu, buf.shape)


def gossip_flat_matrix(W, buf):
    """Dense mixing X <- W X over the packed buffer: ONE [n, n] x
    [n, n_padded] matmul for the whole model (D-PSGD's Metropolis mixing)
    instead of one einsum per pytree leaf."""
    return jnp.einsum("nm,mk->nk", W.astype(jnp.float32), buf)


def _perm_from_pairs(n: int, pairs):
    perm = np.arange(n)
    for s, d in pairs:
        perm[d] = s
    return perm


def pairs_from_perm(perm_arr):
    """Involution perm -> STATIC ppermute (src, dst) pairs. The `[(0, 0)]`
    fallback keeps an all-identity matching a valid (self-send) collective
    instead of an empty pair list, which ppermute rejects."""
    return [(int(perm_arr[d]), int(d)) for d in range(len(perm_arr))
            if perm_arr[d] != d] or [(0, 0)]


# ---------------------------------------------------------------------------
# In-flight payload permutes (the wire half of the non-blocking pipeline)
#
# The pipelined superstep (core/swarm.py, DESIGN.md §Pipeline) carries the
# already-encoded payload of interaction t in SwarmState and dispatches ONLY
# its permute at the top of the superstep, before the local-step loop — the
# encode (previous superstep) and the decode+average (after the loop) live
# outside these helpers, so the collective has no data dependence on the
# local compute and the scheduler is free to overlap the two.
# ---------------------------------------------------------------------------


def permute_rows(x, perm, n_nodes: int):
    """`x` with its node row groups permuted: x is [n_nodes, ...] or
    [n_nodes * r, ...] with node-contiguous row groups (the (q, s) kernel
    layout packs rows_per_node consecutive rows per node). One dynamic
    slice per node, bitwise the gather `x.reshape(n, r, ...)[perm]`: the
    TPU compiler expands a gather of whole model rows into one copy per
    chunk of rows, so its code size and compile time grow with the model,
    and the reshape to [n, r, ...] is itself a relayout."""
    r = x.shape[0] // n_nodes
    return jnp.concatenate(
        [jax.lax.dynamic_slice_in_dim(x, perm[i] * r, r, axis=0)
         for i in range(n_nodes)], axis=0)


def permute_payload_ppermute(payload, mesh, node_axes, pairs, n_nodes: int):
    """ONE collective-permute per in-flight payload tensor and nothing else.
    `payload` is a tuple of node-grouped arrays (fp32 buffer exact; uint8 q
    + fp32 scales quantized); `pairs` is a STATIC involution."""
    from jax.sharding import PartitionSpec as P

    n_shards = 1
    for a in node_axes:
        n_shards *= mesh.shape[a]
    if not node_axes or n_shards == 1:
        # all nodes on one shard: the permute degenerates to a local gather
        perm = jnp.asarray(_perm_from_pairs(n_nodes, pairs))
        return tuple(permute_rows(x, perm, n_nodes) for x in payload)
    axis = node_axes if len(node_axes) > 1 else node_axes[0]
    part = tuple(node_axes) if len(node_axes) > 1 else node_axes[0]
    full_pairs = [(int(s), int(d)) for s, d in pairs]
    specs = tuple(P(part, *([None] * (x.ndim - 1))) for x in payload)

    def f(*xs):
        return tuple(jax.lax.ppermute(x, axis, full_pairs) for x in xs)

    fn = jax.shard_map(f, mesh=mesh, in_specs=specs, out_specs=specs,
                       check_vma=False)
    return fn(*payload)


def permute_payload_pool(payload, mesh, node_axes, pool, pool_idx,
                         n_nodes: int):
    """lax.switch over the static matching pool; each branch holds ONLY the
    payload permutes — encode/decode live outside the switch, so the pool
    compiles K×P collectives instead of K×(encode + P + decode)."""

    def branch(perm_arr):
        pairs = pairs_from_perm(perm_arr)
        return lambda xs: permute_payload_ppermute(xs, mesh, node_axes,
                                                   pairs, n_nodes)

    return jax.lax.switch(pool_idx, [branch(p) for p in pool], payload)


def gossip_flat_ppermute(buf, mesh, node_axes, pairs, *,
                         quant=None, prev_buf=None, rng=None, backend=None,
                         tile_rows: int = DEFAULT_TILE_ROWS, mask=None):
    """shard_map collective-permute over the flat buffer: ONE ppermute per
    payload tensor (fp32 buffer exact; one per codec wire group quantized)
    — vs one per pytree leaf in the legacy transport. `quant` is a
    ModularQuantConfig (lattice) or any non-residual WireCodec. `pairs` is
    a STATIC involution [(src, dst), ...] over node/shard indices. `mask`
    (bool [n_nodes/n_shards], dynamic) further gates which of the static
    pairs land this superstep — the scheduler bridge's partial-
    participation hook: the wire permute still runs (static HLO), unmasked
    receivers keep their own model."""
    from jax.sharding import PartitionSpec as P

    codec = as_codec(quant)
    assert codec is None or not codec.carries_residual, \
        f"{codec.name}: error-feedback codecs run on the gather transport " \
        "(the residual slot does not thread through shard_map; see the " \
        "codec axis of algorithms/registry.py CAPABILITIES)"
    n_nodes = buf.shape[0]
    n_shards = 1
    for a in node_axes:
        n_shards *= mesh.shape[a]
    perm_arr = _perm_from_pairs(n_nodes if (not node_axes or n_shards == 1)
                                else n_shards, pairs)
    if not node_axes or n_shards == 1:
        # all nodes on one shard: the permute degenerates to a local gather
        perm_j = jnp.asarray(perm_arr)
        matched = jnp.asarray(perm_arr != np.arange(len(perm_arr)))
        if mask is not None:
            matched = matched & mask
        if codec is None:
            return gossip_flat_exact(buf, perm_j, matched)
        out, _ = gossip_flat_coded(codec, buf, prev_buf, perm_j, matched,
                                   rng, tile_rows=tile_rows, backend=backend)
        return out

    axis = node_axes if len(node_axes) > 1 else node_axes[0]
    part = tuple(node_axes) if len(node_axes) > 1 else node_axes[0]
    spec = P(part, None)
    full_pairs = [(int(s), int(d)) for s, d in pairs]
    matched_np = perm_arr != np.arange(n_shards)

    def _local_mask(idx, mk):
        m = jnp.asarray(matched_np)[idx]
        return m if mk is None else m & mk.reshape(-1)[idx]

    def exact(x, mk=None):
        xh = jax.lax.ppermute(x, axis, full_pairs)     # the ONE collective
        m = _local_mask(jax.lax.axis_index(axis), mk)
        return jnp.where(m, (x + xh) * 0.5, x)

    def quantized(x, pv, key, mk=None):
        idx = jax.lax.axis_index(axis)
        key = jax.random.fold_in(key, idx) if codec.needs_rng else key
        wire = codec.encode(x, pv, key, tile_rows=tile_rows, backend=backend)
        # ONE collective per codec wire group (q+s lattice; v bf16; ...)
        wire_p = tuple(jax.lax.ppermute(w, axis, full_pairs) for w in wire)
        m = _local_mask(idx, mk)
        m_rows = jnp.broadcast_to(m, (wire[0].shape[0], 1))
        return codec.decode_avg(wire_p, x, m_rows, tile_rows=tile_rows,
                                backend=backend)

    if codec is None:
        f, args, in_specs = exact, (buf,), (spec,)
    else:
        f, args, in_specs = quantized, (buf, prev_buf, rng), (spec, spec, P())
    if mask is not None:
        args, in_specs = args + (mask,), in_specs + (P(),)
    fn = jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=spec,
                       check_vma=False)
    return fn(*args)


def gossip_flat_ppermute_pool(buf, mesh, node_axes, pool, pool_idx, *,
                              quant: Optional[ModularQuantConfig] = None,
                              prev_buf=None, rng=None, backend=None,
                              tile_rows: int = DEFAULT_TILE_ROWS, mask=None):
    """lax.switch over a static matching pool; each branch holds ONE
    collective over the flat buffer (vs one per leaf per branch legacy —
    the K×L → K collective collapse that cuts compile time). `mask` gates
    which of the selected matching's pairs land (sched/bridge.py bins)."""

    def branch(perm_arr):
        pairs = pairs_from_perm(perm_arr)

        def g(b):
            return gossip_flat_ppermute(b, mesh, node_axes, pairs,
                                        quant=quant, prev_buf=prev_buf,
                                        rng=rng, backend=backend,
                                        tile_rows=tile_rows, mask=mask)
        return g

    return jax.lax.switch(pool_idx, [branch(p) for p in pool], buf)
