"""Unified gossip exchange layer (DESIGN.md §Baselines).

Every distributed algorithm in this repo — SwarmSGD and all the baselines
it is compared against — ultimately moves *whole models* between nodes.
Historically only the swarm engine used the bucketed flat-buffer transport
(``core/bucket.py``); the baselines ran hand-rolled per-leaf ``tree.map``
exchanges on the idealized synchronous path. This module extracts the
exchange machinery into a first-class :class:`GossipTransport` so that

* SwarmSGD's superstep (``core/swarm.py``) and every baseline in
  ``algorithms/`` route their communication through the SAME pack /
  permute / decode paths (flat fp32 buffer, or the quantized uint8+scales
  pair through the Pallas kernel wrappers);
* the historical per-leaf implementations remain available as the
  ``*_legacy`` transports — the bit-for-bit oracles the flat paths are
  validated against (tests/test_baseline_parity.py);
* participation masks (the scheduler bridge's partial-participation hook,
  ``sched/bridge.py``) work uniformly, so baselines run under
  heterogeneous Poisson clocks exactly like the swarm engine does.

The transport exposes four exchange primitives, covering every baseline's
communication pattern:

  ``mix_pair``     — permutation-indexed pairwise average (SwarmSGD,
                     AD-PSGD matchings; SGP's directed one-peer push is the
                     same primitive with a non-involutive perm), optionally
                     through the modular quantizer;
  ``global_mean``  — (masked) mean over the node axis, broadcast back
                     (LocalSGD model sync, AllReduce gradient averaging);
  ``matrix_mix``   — dense doubly-stochastic mixing ``X <- W X`` over the
                     packed buffer (D-PSGD Metropolis weights);
  ``permute_inflight`` — the wire half of the overlapped pipeline: permute
                     an already-encoded payload tuple and nothing else.

Legacy oracle functions (``gossip_exact`` & co) live here and are
re-exported from ``core/swarm.py`` for backwards compatibility.
"""
from __future__ import annotations

import os
from functools import partial
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bucket as B
from repro.quant.codecs import LatticeCodec, WireCodec, make_codec
from repro.quant.schemes import (
    ModularQuantConfig, decode_modular, encode_modular,
)

BASE_IMPLS = ("gather", "ppermute", "ppermute_pool")


# ---------------------------------------------------------------------------
# Shared local-step loop + masked-loss convention (swarm engine AND the
# h-consuming baselines — ONE definition, so the idle-lane semantics of the
# scheduler bridge cannot silently diverge between algorithms)
# ---------------------------------------------------------------------------


def make_local_steps(loss_fn, opt_update, h_max: int):
    """One node's h_i <= h_max local SGD steps (no collectives), loop body
    masked beyond h_i; returns (params_i, opt_i, mean loss over the h_i
    active steps). Callers vmap over the node axis. Uses the unroll-aware
    fori_loop so the dry-run's exact-FLOP lowering applies uniformly."""
    from repro.models import unroll as U

    def local_steps(params_i, opt_i, batch_i, h_i, lr):
        def body(q, carry):
            p, o, lsum = carry
            mb = jax.tree.map(lambda x: x[q], batch_i)
            loss, g = jax.value_and_grad(loss_fn)(p, mb)
            p2, o2 = opt_update(p, g, o, lr)
            active = q < h_i
            p = jax.tree.map(lambda a, b: jnp.where(active, b, a), p, p2)
            o = jax.tree.map(lambda a, b: jnp.where(active, b, a), o, o2)
            return (p, o, lsum + jnp.where(active, loss, 0.0))
        params_i, opt_i, lsum = U.fori_loop(
            0, h_max, body, (params_i, opt_i, jnp.zeros((), jnp.float32)))
        return params_i, opt_i, lsum / jnp.maximum(h_i, 1)
    return local_steps


def masked_mean_loss(losses, mask):
    """Loss over PARTICIPANTS (idle lanes carry zeros); the plain mean is
    kept bitwise for mask=None — the one loss convention every algorithm
    reports under the scheduler bridge."""
    if mask is None:
        return jnp.mean(losses)
    return jnp.sum(jnp.where(mask, losses, 0.0)) / \
        jnp.maximum(jnp.sum(mask.astype(jnp.int32)), 1)


# ---------------------------------------------------------------------------
# Legacy per-leaf gossip oracles (one collective per pytree leaf)
# ---------------------------------------------------------------------------


def _avg(x, xp, matched):
    """(x + x[perm])/2 where matched, else x."""
    out = (x.astype(jnp.float32) + xp.astype(jnp.float32)) * 0.5
    m = matched.reshape((-1,) + (1,) * (x.ndim - 1))
    return jnp.where(m, out.astype(x.dtype), x)


def gossip_exact(params, perm, matched):
    return jax.tree.map(lambda x: _avg(x, x[perm], matched), params)


def gossip_ppermute(params, param_specs, mesh, node_axes, pairs,
                    quant: Optional[ModularQuantConfig] = None, prev=None,
                    rng=None):
    """LEGACY per-leaf transport (oracle for core/bucket.py's flat buffer).

    Pairwise gossip via `collective-permute` under shard_map — the direct
    TPU analogue of the paper's MPI sendrecv exchange: each matched node
    sends exactly ONE model copy (or its uint8 encoding) to its partner,
    instead of the O(n)-traffic all-gather that a dynamic `x[perm]` gather
    lowers to. `pairs` is a STATIC involution [(src, dst), ...] (production
    uses a lax.switch over a precompiled matching pool; see DESIGN.md §Perf).
    Issues one collective PER LEAF — the flat-buffer transport replaces this
    with one collective per payload tensor for the whole model.
    """
    from jax.sharding import PartitionSpec as P

    n_nodes = 1
    for a in node_axes:
        n_nodes *= mesh.shape[a]
    if not node_axes or n_nodes == 1:
        # all nodes live on one shard (CPU runs / single-node-per-mesh):
        # the "permute" degenerates to a local static-perm average
        leaves = jax.tree.leaves(params)
        n = leaves[0].shape[0]
        perm_arr = np.arange(n)
        for s, d in pairs:
            perm_arr[d] = s
        perm_j = jnp.asarray(perm_arr)
        matched = jnp.asarray(perm_arr != np.arange(n))
        return gossip_exact(params, perm_j, matched) if quant is None else \
            gossip_quantized(quant, params, prev, perm_j, matched, rng)
    perm_arr = np.arange(n_nodes)
    for s, d in pairs:
        perm_arr[d] = s
    matched_np = perm_arr != np.arange(n_nodes)
    axis = node_axes if len(node_axes) > 1 else node_axes[0]
    full_pairs = [(int(s), int(d)) for s, d in pairs]

    def per_leaf(spec):
        def f(x, pv, key):
            # x: local shard [n_local=1 or n/|node|, ...]
            if quant is not None:
                nkeys = jax.random.split(key, x.shape[0])
                q, s = jax.vmap(partial(encode_modular, quant))(x, pv, nkeys)
                qp = jax.lax.ppermute(q, axis, full_pairs)
                sp = jax.lax.ppermute(s, axis, full_pairs)
                xh = jax.vmap(partial(decode_modular, quant))(qp, sp, x)
            else:
                xh = jax.lax.ppermute(x, axis, full_pairs)
            idx = jax.lax.axis_index(axis)
            m = jnp.asarray(matched_np)[idx]
            out = (x.astype(jnp.float32) + xh.astype(jnp.float32)) * 0.5
            return jnp.where(m, out.astype(x.dtype), x)
        return f

    leaves, tdef = jax.tree.flatten(params)
    specs = jax.tree.leaves(param_specs, is_leaf=lambda s: isinstance(s, P))
    prev_leaves = jax.tree.leaves(prev) if prev is not None else [None] * len(leaves)
    keys = (list(jax.random.split(rng, len(leaves))) if rng is not None
            else [jnp.zeros((2,), jnp.uint32)] * len(leaves))
    out = []
    for x, spec, pv, key in zip(leaves, specs, prev_leaves, keys):
        if quant is not None:
            fn = jax.shard_map(per_leaf(spec), mesh=mesh,
                               in_specs=(spec, spec, P()), out_specs=spec,
                               check_vma=False)
            out.append(fn(x, pv, key))
        else:
            fn = jax.shard_map(
                lambda x_: per_leaf(spec)(x_, None, None), mesh=mesh,
                in_specs=(spec,), out_specs=spec, check_vma=False)
            out.append(fn(x))
    return jax.tree.unflatten(tdef, out)


def make_matching_pool(graph, K: int, seed: int = 0):
    """K precompiled random matchings of G (as involution perms). Production
    ppermute gossip selects one per superstep via lax.switch — dynamic
    partner choice with STATIC collective-permute HLO. For a complete graph
    and K >= n-1 this can be a 1-factorization (round-robin tournament),
    whose uniform selection has the same single-edge marginals as the
    paper's uniform edge sampling."""
    from repro.core.graph import sample_matching
    rng = np.random.default_rng(seed)
    return [sample_matching(graph, rng) for _ in range(K)]


def gossip_ppermute_pool(params, param_specs, mesh, node_axes, pool,
                         pool_idx, quant=None, prev=None, rng=None):
    """lax.switch over a static matching pool; each branch is a
    gossip_ppermute with its own static source-target pairs."""
    def branch(perm_arr):
        pairs = B.pairs_from_perm(perm_arr)

        def f(p):
            return gossip_ppermute(p, param_specs, mesh, node_axes, pairs,
                                   quant=quant, prev=prev, rng=rng)
        return f

    return jax.lax.switch(pool_idx, [branch(p) for p in pool], params)


def gossip_quantized(qcfg, params, prev, perm, matched, rng):
    """LEGACY per-leaf quantized transport (oracle for the flat buffer):
    exchange the 8-bit modular encoding instead of raw values.

    Each node encodes its model against its own `prev` comm copy (the
    sender-local distance proxy); the *uint8 payload + fp32 block scales*
    are what move along the node axis; the receiver decodes against its own
    model (the lattice reference) and averages.
    """
    leaves, tdef = jax.tree.flatten(params)
    prev_leaves = jax.tree.leaves(prev)
    keys = jax.random.split(rng, len(leaves))
    out = []
    for x, pv, key in zip(leaves, prev_leaves, keys):
        nkeys = jax.random.split(key, x.shape[0])
        q, s = jax.vmap(partial(encode_modular, qcfg))(x, pv, nkeys)
        qp, sp = q[perm], s[perm]          # <- quantized payload crosses nodes
        xh = jax.vmap(partial(decode_modular, qcfg))(qp, sp, x)
        out.append(_avg(x, xh, matched))
    return jax.tree.unflatten(tdef, out)


def static_ppermute_matching(graph, seed: int) -> np.ndarray:
    """THE static involution the plain-ppermute transport is compiled
    against — shared by the transport factory (which bakes it into the
    collective) and the driver's `sample_gossip_perm` (which must feed the
    engine the same matching, or the matched mask would disagree with the
    actual data movement)."""
    from repro.core.graph import sample_matching
    return sample_matching(graph, np.random.default_rng(seed))


# ---------------------------------------------------------------------------
# GossipTransport — the first-class exchange layer
# ---------------------------------------------------------------------------


class GossipTransport:
    """One object owning a gossip implementation's full wiring.

    `impl` is the engine's ``gossip_impl`` string: ``gather`` (GSPMD
    gather), ``ppermute`` (shard_map, one static matching) or
    ``ppermute_pool`` (lax.switch over a static matching pool), each on the
    bucketed flat-buffer transport; append ``_legacy`` for the historical
    per-leaf oracle paths. ``None`` resolves through the
    ``REPRO_DEFAULT_GOSSIP_IMPL`` env override, same as ``SwarmConfig``.

    The shard_map modes require (mesh, node_axes) plus their static wiring
    (``static_pairs`` / ``matching_pool``); the legacy (or >8-bit quant)
    modes additionally require ``param_specs``. Build via
    :func:`transport_from_config` for the standard driver plumbing.
    """

    def __init__(self, impl: Optional[str] = None, n_nodes: int = 0, *,
                 quant: Optional[ModularQuantConfig] = None,
                 codec: Optional[WireCodec] = None,
                 mesh=None, node_axes=None, static_pairs=None,
                 matching_pool=None, param_specs=None):
        impl = impl if impl is not None else os.environ.get(
            "REPRO_DEFAULT_GOSSIP_IMPL", "gather")
        self.impl = impl
        self.legacy = impl.endswith("_legacy")
        self.base_impl = impl[:-len("_legacy")] if self.legacy else impl
        assert self.base_impl in BASE_IMPLS, impl
        self.n_nodes = n_nodes
        # the wire codec owns the format; `quant` keeps seeding the lattice
        # family (and the per-leaf legacy oracles, which speak encode/
        # decode_modular and therefore carry lattice codecs only)
        self.codec = codec if codec is not None \
            else LatticeCodec(quant or ModularQuantConfig())
        self.quant = self.codec.quant \
            if isinstance(self.codec, LatticeCodec) \
            else (quant or ModularQuantConfig(block=self.codec.block))
        if self.legacy and not isinstance(self.codec, LatticeCodec):
            raise ValueError(
                f"codec {self.codec.name!r} has no per-leaf form: the "
                "*_legacy oracles exchange encode_modular payloads "
                "(lattice q2..q16 only; see the codec axis of "
                "algorithms/registry.py CAPABILITIES)")
        if self.codec.carries_residual and self.base_impl != "gather":
            raise ValueError(
                f"codec {self.codec.name!r} carries an error-feedback "
                "residual, which only the gather transport threads "
                f"(got --gossip-impl {impl}; see the codec axis of "
                "algorithms/registry.py CAPABILITIES)")
        self.mesh = mesh
        self.node_axes = node_axes
        self.static_pairs = static_pairs
        self.matching_pool = matching_pool
        self.param_specs = param_specs
        self._stacked_pool = None
        if self.base_impl == "ppermute":
            assert mesh is not None and node_axes is not None \
                and static_pairs is not None, \
                "ppermute transport requires (mesh, node_axes, static_pairs)"
        if self.base_impl == "ppermute_pool":
            assert mesh is not None and node_axes is not None \
                and matching_pool is not None, \
                "ppermute_pool transport requires (mesh, node_axes, " \
                "matching_pool)"
            self._stacked_pool = jnp.asarray(np.stack(matching_pool))

    # -- capability / validation helpers ----------------------------------

    def routes_per_leaf(self, quantize: bool) -> bool:
        """True when this exchange runs the per-leaf path — ONLY the
        *_legacy oracles now: the flat transport carries every codec
        (uint16 lattice included; the historical silent bits>8 per-leaf
        fallback is gone — unsupported widths fail at codec construction
        instead, never by degrading the transport)."""
        del quantize
        return self.legacy

    def check_specs(self, quantize: bool):
        if self.base_impl != "gather" and self.routes_per_leaf(quantize):
            assert self.param_specs is not None, \
                "legacy per-leaf shard_map gossip requires param_specs"

    def check_overlap(self, quantize: bool):
        assert not self.legacy, \
            "the pipelined overlap mode runs on the flat transport only " \
            "(no *_legacy per-leaf oracles)"
        assert not (quantize and self.codec.carries_residual), \
            f"codec {self.codec.name}: the error-feedback residual " \
            "updates at encode time against the matched mask, which the " \
            "pipelined superstep only learns one interaction later — " \
            "run top-k under blocking/nonblocking (capability matrix)"

    # -- perm plumbing -----------------------------------------------------

    def resolve_perm(self, perm) -> Tuple[Any, Any]:
        """`perm` carries the scalar pool index in ppermute_pool mode;
        recover the actual node->partner permutation from the pool."""
        if self.base_impl == "ppermute_pool":
            pool_idx = perm.reshape(-1)[0]
            return self._stacked_pool[pool_idx], pool_idx
        return perm, None

    # -- exchange primitives ----------------------------------------------

    def mix_pair(self, tree, perm, matched, *, quantize: bool = False,
                 prev=None, prev_buf=None, rng=None, mask=None,
                 residual=None):
        """Average each node's `tree` entry with its partner's — over the
        flat-buffer transport unless a *_legacy oracle is selected. `perm`
        is the raw engine input (it carries the scalar pool index in
        ppermute_pool modes); `matched` is the already-gated landing mask
        ((perm != arange) & mask for matchings; an arbitrary gate for
        directed exchanges). `mask` is additionally threaded to the flat
        shard_map transports, whose wire pairs are compiled in, so a
        dynamic gate can land a PARTIAL matching.

        The quantized encode's distance proxy comes from `prev` (a
        tree-shaped comm copy, packed here) or — under compress_state
        (core/swarm.py; DESIGN.md §Hierarchy) — from `prev_buf`, the
        already-packed [n_nodes, n_padded] fp32 buffer the superstep
        lazily decoded from the wire-compressed copy. Flat transports
        only: the per-leaf legacy oracles have no packed form.

        When the transport's codec carries an error-feedback residual
        (`self.codec.carries_residual`) the call takes and RETURNS the
        buffer-shaped residual: -> (mixed_tree, new_residual); every other
        codec returns the mixed tree alone (the pre-codec signature)."""
        if mask is not None and self.base_impl != "gather" and \
                self.routes_per_leaf(quantize):
            raise NotImplementedError(
                "participation masks are supported on the flat transports "
                "and the gather_legacy oracle only; the per-leaf ppermute "
                "legacy oracles bake a full static matching")
        ef = quantize and self.codec.carries_residual
        quant = self.codec if quantize else None
        if prev_buf is not None:
            assert not self.routes_per_leaf(quantize), \
                "prev_buf (compress_state) needs the flat packed transport"
        if self.routes_per_leaf(quantize):
            # per-leaf oracles speak the lattice scheme only (checked in
            # __init__), and never carry a residual
            lat = self.quant if quantize else None
            if self.base_impl == "ppermute":
                return gossip_ppermute(tree, self.param_specs, self.mesh,
                                       self.node_axes, self.static_pairs,
                                       quant=lat, prev=prev, rng=rng)
            if self.base_impl == "ppermute_pool":
                return gossip_ppermute_pool(
                    tree, self.param_specs, self.mesh, self.node_axes,
                    self.matching_pool, perm.reshape(-1)[0],
                    quant=lat, prev=prev, rng=rng)
            if quantize:
                return gossip_quantized(lat, tree, prev, perm,
                                        matched, rng)
            return gossip_exact(tree, perm, matched)
        layout = B.build_layout(tree, block=self.codec.block)
        buf = B.pack(layout, tree)
        pbuf = prev_buf if prev_buf is not None else \
            (B.pack(layout, prev) if quantize else None)
        new_residual = None
        if self.base_impl == "gather":
            if quantize:
                buf, new_residual = B.gossip_flat_coded(
                    self.codec, buf, pbuf, perm, matched, rng,
                    residual=residual)
            else:
                buf = B.gossip_flat_exact(
                    buf, perm, matched if mask is not None else None)
        elif self.base_impl == "ppermute":
            buf = B.gossip_flat_ppermute(
                buf, self.mesh, self.node_axes, self.static_pairs,
                quant=quant, prev_buf=pbuf, rng=rng, mask=mask)
        else:
            buf = B.gossip_flat_ppermute_pool(
                buf, self.mesh, self.node_axes, self.matching_pool,
                perm.reshape(-1)[0], quant=quant, prev_buf=pbuf, rng=rng,
                mask=mask)
        out = B.unpack(layout, buf)
        return (out, new_residual) if ef else out

    def global_mean(self, tree, mask=None):
        """(Masked) mean over the node axis, broadcast back to every node —
        LocalSGD's periodic resync and AllReduce's gradient averaging. With
        `mask`, the mean runs over PARTICIPANTS only and is still broadcast
        everywhere (the server-broadcast / backup-workers semantics of
        partial-participation synchronous training)."""
        if self.legacy:
            if mask is None:
                return jax.tree.map(
                    lambda x: jnp.broadcast_to(
                        jnp.mean(x.astype(jnp.float32), axis=0,
                                 keepdims=True),
                        x.shape).astype(x.dtype), tree)
            w = mask.astype(jnp.float32)
            denom = jnp.maximum(jnp.sum(w), 1.0)

            def leaf_mean(x):
                wx = w.reshape((-1,) + (1,) * (x.ndim - 1)) * \
                    x.astype(jnp.float32)
                mu = jnp.sum(wx, axis=0, keepdims=True) / denom
                return jnp.broadcast_to(mu, x.shape).astype(x.dtype)
            return jax.tree.map(leaf_mean, tree)
        layout = B.build_layout(tree, block=self.codec.block)
        return B.unpack(layout, B.gossip_flat_mean(B.pack(layout, tree),
                                                   mask))

    def matrix_mix(self, tree, W):
        """Dense doubly-stochastic mixing X <- W X (D-PSGD): ONE [n, n] ×
        [n, n_padded] matmul over the packed buffer instead of one einsum
        per pytree leaf."""
        if self.legacy:
            return jax.tree.map(
                lambda x: jnp.einsum(
                    "nm,m...->n...", W,
                    x.astype(jnp.float32)).astype(x.dtype), tree)
        layout = B.build_layout(tree, block=self.codec.block)
        return B.unpack(layout, B.gossip_flat_matrix(W, B.pack(layout,
                                                               tree)))

    def permute_inflight(self, payload: Sequence[jax.Array], perm):
        """The wire half of the overlapped pipeline: ONE permute per
        already-encoded payload tensor and nothing else (encode/decode live
        outside; DESIGN.md §Pipeline)."""
        node_perm, pool_idx = self.resolve_perm(perm)
        if self.base_impl == "gather":
            return tuple(B.permute_rows(x, node_perm, self.n_nodes)
                         for x in payload)
        if self.base_impl == "ppermute":
            return B.permute_payload_ppermute(
                payload, self.mesh, self.node_axes, self.static_pairs,
                self.n_nodes)
        return B.permute_payload_pool(
            payload, self.mesh, self.node_axes, self.matching_pool,
            pool_idx, self.n_nodes)

    def payload_num_bytes(self, tree, quantize: bool = False) -> int:
        """Exact wire bytes per node for one gossip send of `tree` —
        priced from the codec's declared WireLayout (quant/codecs.py)."""
        layout = B.build_layout(tree, block=self.codec.block)
        return layout.payload_num_bytes(self.codec if quantize else None)

    def residual_like(self, tree):
        """Zero-initialized error-feedback residual for `tree` (the
        buffer-shaped [n_nodes, n_padded] slot SwarmState carries when
        the codec does), or None for residual-free codecs."""
        if not self.codec.carries_residual:
            return None
        layout = B.build_layout(tree, block=self.codec.block)
        return jnp.zeros((layout.n_nodes, layout.n_padded), jnp.float32)


def transport_from_config(scfg, graph, seed: int = 0, param_probe=None,
                          devices=None) -> GossipTransport:
    """Standard driver plumbing: a transport for `scfg.gossip_impl` on the
    swarm's node mesh (launch/mesh.py `node_mesh`). On one device the mesh
    has one shard and the collective degenerates to a local permute; over
    several devices (`devices`, default every device the process sees)
    each holds one node and the shard_map transports permute across them.
    `param_probe` is an abstract single-node param tree, only needed for
    the per-leaf legacy shard_map modes, which shard each leaf by its own
    node-leading spec.

    The wire format comes from `scfg.codec` (+ `scfg.quant` seeding the
    lattice family). Every supported codec runs the FLAT transport — the
    historical silent bits>8 per-leaf fallback is gone: an unsupported
    width/impl combination raises HERE, at config time, naming the codec
    matrix, never by quietly degrading to the slow path."""
    impl = scfg.gossip_impl
    base = impl[:-len("_legacy")] if impl.endswith("_legacy") else impl
    quant = getattr(scfg, "quant", None)
    codec = make_codec(getattr(scfg, "codec", None), quant)
    kw = dict(quant=quant, codec=codec)
    from repro.launch.mesh import auto_mesh, node_mesh
    mesh = node_mesh(scfg.n_nodes, devices)
    if mesh is not None:
        kw.update(mesh=mesh, node_axes=("node",))
    if base != "gather":
        from jax.sharding import PartitionSpec as P

        if mesh is None:
            kw.update(mesh=auto_mesh((1,), ("node",)), node_axes=())
        node = "node" if mesh is not None else None
        if param_probe is not None:
            kw["param_specs"] = jax.tree.map(
                lambda x: P(node, *((None,) * x.ndim)), param_probe)
        if base == "ppermute":
            kw["static_pairs"] = B.pairs_from_perm(
                static_ppermute_matching(graph, seed))
        else:
            from repro.core.hier import parse_topology
            topo = parse_topology(getattr(scfg, "topology", None),
                                  scfg.n_nodes)
            K = getattr(scfg, "pool_size", 8)
            if topo is not None:
                # hier pool: K intra matchings (rng-identical to the flat
                # pool for a single group) + the inter-group perm suffix
                kw["matching_pool"], _ = topo.matching_pool(K, seed)
            else:
                kw["matching_pool"] = make_matching_pool(graph, K=K,
                                                         seed=seed)
    return GossipTransport(impl, scfg.n_nodes, **kw)
