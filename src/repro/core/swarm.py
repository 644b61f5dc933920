"""SwarmSGD SPMD training engine.

One jitted *superstep* implements the paper's protocol for all n nodes in
parallel (the paper: "Θ(n) of these interactions could occur in parallel"):

  1. every node runs H local SGD steps on its own model/data — a
     `lax.fori_loop` with ZERO collectives (the communication-frequency
     reduction that is the paper's point);
  2. a uniformly sampled (partial) matching of the interaction graph G is
     applied: matched pairs average their models — blocking (Algorithm 1),
     non-blocking/stale (Algorithm 2), optionally over the 8-bit modular
     quantization of Extension 3 (the uint8 payload is what crosses the
     node mesh axis).

Node state is *node-stacked*: every param/optimizer leaf has a leading
[n_nodes] dim, sharded over the node mesh axes. Local steps are vmapped over
that axis; gossip is a permutation-indexed average along it (lowered by
GSPMD to collectives over the node axes; see §Perf for the shard_map
ppermute variant).

Geometric local steps (Thm 4.1's H_i ~ Geom(H)) are supported by passing
per-node step counts h_i <= h_max and masking the loop body; fixed H
(Thm 4.2 / non-iid) is h_i = H for all i.

Transport: the exchange machinery lives in `core/exchange.py` — a
first-class :class:`~repro.core.exchange.GossipTransport` wrapping the
bucketed flat-buffer pack/permute/decode paths (core/bucket.py, DESIGN.md
§Perf): the node-stacked pytree is packed once per superstep into a single
padded [n_nodes, n_padded] fp32 buffer, so the exchange is ONE collective
over ONE contiguous payload — fp32 exact, or the packed (uint8 q, fp32
block-scales) pair through the Pallas kernel wrappers (kernels/ops.py).
The same transport drives every baseline algorithm in `algorithms/`
(DESIGN.md §Baselines). The historical one-collective-per-leaf transports
remain available as gossip_impl="gather_legacy" / "ppermute_legacy" /
"ppermute_pool_legacy" oracles for tests and A/B benchmarks.
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import bucket as B
from repro.core.exchange import (  # noqa: F401  (re-exports: tests import
    GossipTransport, _avg, gossip_exact, gossip_ppermute,  # these from here)
    gossip_ppermute_pool, gossip_quantized, make_local_steps,
    make_matching_pool, masked_mean_loss,
)
from repro.core.potential import gamma_potential
from repro.quant.codecs import make_codec
from repro.quant.schemes import ModularQuantConfig

Identity = lambda x, kind: x  # noqa: E731


@dataclass(frozen=True)
class SwarmConfig:
    n_nodes: int
    H: int = 2                   # (mean) local steps per interaction
    h_mode: str = "fixed"        # fixed | geometric | trace (h supplied by
    # the scheduler bridge, sched/bridge.py — any non-"fixed" mode bounds
    # the local-step loop by h_max instead of H)
    h_max: int = 8               # static loop bound for variable h modes
    nonblocking: bool = False    # Algorithm 2 semantics
    overlap: bool = False        # pipelined non-blocking superstep: the
    # encoded payload of interaction t is carried in SwarmState.inflight and
    # its collective is dispatched BEFORE the local-step loop of interaction
    # t+1 (double-buffered comm copy; DESIGN.md §Pipeline). Requires
    # nonblocking=True and a flat (non-legacy, bits<=8) transport.
    quantize: bool = False       # Extension 3
    quant: ModularQuantConfig = ModularQuantConfig()
    # wire codec for the quantized exchange (quant/codecs.py): None follows
    # `quant` (the lattice scheme at quant.bits — the pre-codec default);
    # "q2".."q16" | "bf16" | "topk:<frac>" select explicitly. Env default:
    # REPRO_CODEC (like REPRO_DEFAULT_GOSSIP_IMPL for the transport).
    codec: Optional[str] = field(default_factory=lambda: os.environ.get(
        "REPRO_CODEC") or None)
    average_momentum: bool = False  # paper averages MODELS only
    track_potential: bool = True
    # gather (GSPMD gather) | ppermute (shard_map, one static matching) |
    # ppermute_pool (lax.switch over a static matching pool; the production
    # transport: dynamic partner choice, static collective HLO).
    # All three run on the bucketed flat-buffer transport (core/bucket.py):
    # one collective per payload tensor for the WHOLE model. Append
    # "_legacy" (e.g. "gather_legacy") for the per-leaf oracle transports.
    # REPRO_DEFAULT_GOSSIP_IMPL overrides the default (CI runs the tier-1
    # suite once with the legacy per-leaf oracles as the default).
    gossip_impl: str = field(default_factory=lambda: os.environ.get(
        "REPRO_DEFAULT_GOSSIP_IMPL", "gather"))
    pool_size: int = 8
    # two-tier hierarchical gossip (core/hier.py; DESIGN.md §Hierarchy):
    # None = flat single-tier node axis; "hier:G[:inter_frac]" groups nodes
    # by G — intra-group matchings on the fast tier, `inter_frac` of events
    # lane-aligned cross-group exchanges on the slow tier. The engine sees
    # ordinary perms; the topology shapes how the driver SAMPLES them and
    # how the scheduler prices/bins them. Env default: REPRO_TOPOLOGY.
    topology: Optional[str] = field(default_factory=lambda: os.environ.get(
        "REPRO_TOPOLOGY") or None)
    # store the `prev` comm copy codec-compressed (wire tuple encoded vs a
    # zero reference, decoded lazily inside the superstep) instead of a
    # full fp32 tree copy — the ~4x (q8) state shrink that lets a
    # 1024-node swarm lower on a 512-device mesh (launch/dryrun.py).
    # Requires quantize + a lattice codec + blocking + a flat transport
    # (validated in algorithms/registry.py).
    compress_state: bool = False

    @property
    def h_loop_bound(self) -> int:
        """Static bound of the local-step fori_loop (and the batch's
        per-superstep depth): H for fixed h, h_max for the variable modes
        (geometric sampling / scheduler traces). THE single source of
        truth — engine, driver, and benchmarks all resolve through it."""
        return self.H if self.h_mode == "fixed" else self.h_max


@dataclass
class SwarmState:
    params: Any                  # node-stacked pytree
    opt: Any                     # node-stacked optimizer state
    prev: Any                    # comm copy: params at last interaction
    step: jax.Array
    # overlap mode only (DESIGN.md §Pipeline): the double-buffered comm
    # state — {"sbuf": packed params at the last superstep boundary,
    # and when quantized "prev": packed comm copy (the encode proxy),
    # "wire": the encoded in-flight payload tuple awaiting its collective}.
    inflight: Any = None
    # error-feedback codecs only (DESIGN.md §Codec): the untransmitted
    # remainder of the last encode, buffer-shaped [n_nodes, n_padded] fp32
    # — re-enters the next encode; checkpoint it alongside prev so a
    # resumed run continues the top-k event sequence bit-exactly
    # (codec_checkpoint_tree below).
    residual: Any = None

    def tree_flatten(self):
        return (self.params, self.opt, self.prev, self.step,
                self.inflight, self.residual), None


jax.tree_util.register_pytree_node(
    SwarmState, SwarmState.tree_flatten,
    lambda aux, children: SwarmState(*children))


def _stack_init(rng, n_nodes, init_fn, same_init: bool = True):
    if same_init:
        one = init_fn(rng)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_nodes,) + x.shape).copy(), one)
    rngs = jax.random.split(rng, n_nodes)
    return jax.vmap(init_fn)(rngs)


def swarm_init(rng, cfg: SwarmConfig, param_init: Callable, opt_init: Callable,
               same_init: bool = True) -> SwarmState:
    params = _stack_init(rng, cfg.n_nodes, param_init, same_init)
    # probe the optimizer-state STRUCTURE abstractly — no second real init
    probe = jax.eval_shape(opt_init, jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), params))
    opt = jax.vmap(opt_init)(params) if _has_leaves(probe) else {}
    if cfg.overlap:
        # pipelined mode: the comm copy lives packed inside `inflight`
        state = SwarmState(params, opt, None, jnp.zeros((), jnp.int32))
        return pipeline_prologue(cfg, state, jax.random.fold_in(rng, 0x1F))
    prev = None
    residual = None
    if cfg.compress_state:
        # compressed comm copy: the wire tuple of the packed params encoded
        # vs a zero reference (WireCodec.encode_state) — decoded lazily at
        # the top of each superstep, refreshed row-masked on interaction
        assert cfg.quantize and not cfg.nonblocking, \
            "compress_state stores the quantized blocking comm copy " \
            "(validated in algorithms/registry.py)"
        codec = make_codec(cfg.codec, cfg.quant)
        assert not codec.carries_residual, \
            "compress_state is lattice-only (no error-feedback slot)"
        layout = B.build_layout(params, block=codec.block)
        prev = codec.encode_state(B.pack(layout, params),
                                  jax.random.fold_in(rng, 0x5E))
    elif cfg.quantize or cfg.nonblocking:
        prev = jax.tree.map(jnp.copy, params)
    if cfg.quantize:
        codec = make_codec(cfg.codec, cfg.quant)
        if codec.carries_residual:
            layout = B.build_layout(params, block=codec.block)
            residual = jnp.zeros((cfg.n_nodes, layout.n_padded), jnp.float32)
    return SwarmState(params, opt, prev, jnp.zeros((), jnp.int32),
                      residual=residual)


def codec_checkpoint_tree(state: SwarmState) -> dict:
    """What a quantized run must persist to resume its codec state
    bit-exactly: params, the comm copy (the lattice scale / top-k delta
    reference) and — for error-feedback codecs — the residual. Feed to
    checkpoint.save_checkpoint; restore with load_checkpoint against the
    same structure and `restore_codec_state` (tests/test_codecs.py)."""
    tree = {"params": state.params}
    if state.prev is not None:
        tree["prev"] = state.prev
    if state.residual is not None:
        tree["residual"] = state.residual
    return tree


def restore_codec_state(state: SwarmState, tree: dict) -> SwarmState:
    """Inverse of `codec_checkpoint_tree`: overlay the persisted codec
    state onto a freshly initialized SwarmState (same config)."""
    return SwarmState(tree["params"], state.opt,
                      tree.get("prev", state.prev), state.step,
                      state.inflight, tree.get("residual", state.residual))


def pipeline_prologue(cfg: SwarmConfig, state: SwarmState, rng) -> SwarmState:
    """Software-pipeline PROLOGUE: pack (and, quantized, encode) the first
    in-flight payload so the first superstep can dispatch its collective
    before any local compute. `swarm_init` calls this automatically when
    cfg.overlap; it is also the re-entry point after `pipeline_epilogue`."""
    assert cfg.nonblocking, "overlap pipelining implements Algorithm 2: " \
        "set nonblocking=True"
    codec = make_codec(cfg.codec, cfg.quant)
    layout = B.build_layout(state.params, block=codec.block)
    buf = B.pack(layout, state.params)
    if cfg.quantize:
        # the first comm copy is a DISTINCT buffer even when it starts
        # equal to the model: the scan driver donates the whole SwarmState,
        # and XLA rejects donating one concrete buffer through two tree
        # slots (core/scan.py)
        prev_buf = B.pack(layout, state.prev) if state.prev is not None \
            else jnp.copy(buf)
        wire = codec.encode(buf, prev_buf, rng)
        infl = {"sbuf": buf, "prev": prev_buf, "wire": wire}
    else:
        infl = {"sbuf": buf}
    return SwarmState(state.params, state.opt, None, state.step, infl)


def pipeline_epilogue(cfg: SwarmConfig, state: SwarmState) -> SwarmState:
    """Software-pipeline EPILOGUE (drain): drop the in-flight payload. The
    model state is already final — the payload only fed the NEXT interaction,
    which will not happen. The packed comm copy (the quant encode's distance
    proxy) is unpacked back into `prev` so a later `pipeline_prologue`
    re-primes with a LIVE proxy — re-priming from the model itself would
    collapse the scale to min_scale and wrap the first post-resume decode.
    Use before checkpointing/serving a pipelined run."""
    prev = state.prev
    if state.inflight is not None and "prev" in state.inflight:
        codec = make_codec(cfg.codec, cfg.quant)
        layout = B.build_layout(state.params, block=codec.block)
        prev = B.unpack(layout, state.inflight["prev"])
    return SwarmState(state.params, state.opt, prev, state.step, None)


def _has_leaves(tree) -> bool:
    return len(jax.tree.leaves(tree)) > 0


# ---------------------------------------------------------------------------
# Superstep factory
# ---------------------------------------------------------------------------


def make_swarm_step(cfg: SwarmConfig, loss_fn: Callable, opt_update: Callable,
                    lr_fn: Callable, shard: Callable = Identity, *,
                    mesh=None, param_specs=None, node_axes=None,
                    static_pairs=None, matching_pool=None,
                    transport: Optional[GossipTransport] = None):
    """Returns superstep(state, batch, perm, h_counts, rng, mask=None)
    -> (state, metrics).

    loss_fn(params, microbatch) -> scalar; batch leaves are
    [n_nodes, h_max, local_batch, ...]; perm: [n_nodes] int32 involution;
    h_counts: [n_nodes] int32 (# local steps this superstep, <= h_max;
    0 = node idle this superstep).

    `mask` (optional bool [n_nodes]) is the scheduler bridge's
    participation gate (sched/bridge.py): the effective matching is
    `(perm != arange) & mask`, so the static-matching transports (ppermute,
    ppermute_pool — whose wire pairs are compiled in) can land a PARTIAL
    matching: every pair still exchanges on the wire, but only pairs whose
    endpoints interacted this bin average. With mask=None (default) or an
    all-True mask the computation is bitwise identical to the unmasked
    engine. Supported on the flat transports and the gather_legacy oracle;
    the per-leaf ppermute legacy oracles reject it.

    The exchange runs through a :class:`GossipTransport` (core/exchange.py)
    — pass one via `transport`, or pass the raw wiring (mesh, node_axes,
    static_pairs / matching_pool, and param_specs for the per-leaf legacy
    or >8-bit modes) and one is built from cfg.gossip_impl. All modes run
    on the bucketed flat-buffer transport; the "*_legacy" variants keep the
    historical per-leaf collectives.

    With cfg.overlap the returned step is the software-pipelined steady
    state: it consumes `state.inflight` (primed by swarm_init /
    pipeline_prologue) and dispatches that payload's collective before the
    local-step loop — see DESIGN.md §Pipeline.
    """
    h_max = cfg.h_loop_bound
    tr = transport or GossipTransport(
        cfg.gossip_impl, cfg.n_nodes, quant=cfg.quant,
        codec=make_codec(cfg.codec, cfg.quant), mesh=mesh,
        node_axes=node_axes, static_pairs=static_pairs,
        matching_pool=matching_pool, param_specs=param_specs)
    assert tr.base_impl in ("gather", "ppermute", "ppermute_pool"), \
        cfg.gossip_impl
    tr.check_specs(cfg.quantize)
    ef = cfg.quantize and tr.codec.carries_residual   # error-feedback codec
    cs = cfg.compress_state                    # wire-compressed comm copy
    if cs:
        assert cfg.quantize and not cfg.nonblocking and not cfg.overlap, \
            "compress_state: quantized blocking path only " \
            "(validated in algorithms/registry.py)"
        assert not tr.codec.carries_residual, \
            "compress_state is lattice-only (no error-feedback slot)"
        assert not tr.legacy, \
            "compress_state needs the flat packed transport (the per-leaf " \
            "legacy oracles keep a tree-shaped comm copy)"
    if cfg.overlap:
        assert cfg.nonblocking, \
            "overlap=True pipelines Algorithm 2: set nonblocking=True"
        tr.check_overlap(cfg.quantize)

    # one node's H local SGD steps (no collectives) — THE shared loop
    # (core/exchange.py), also used by the h-consuming baselines
    local_steps = make_local_steps(loss_fn, opt_update, h_max)
    vmapped = jax.vmap(local_steps, in_axes=(0, 0, 0, 0, None))
    if tr.node_axes and tr.mesh is not None and \
            set(tr.mesh.axis_names) == set(tr.node_axes):
        # a pure node mesh (one node per device, launch/mesh.py node_mesh):
        # each device runs its own node's local steps. Under plain GSPMD
        # the optimizer's Pallas kernel (a custom call the partitioner
        # cannot split) would be gathered and run for every node everywhere
        from jax.sharding import PartitionSpec as P
        node = P(tr.node_axes)
        vmapped = jax.shard_map(vmapped, mesh=tr.mesh,
                                in_specs=(node, node, node, node, P()),
                                out_specs=(node, node, node),
                                check_vma=False)

    def run_local_steps(state, batch, h_counts, lr):
        params, opt, losses = vmapped(state.params, state.opt, batch,
                                      h_counts, lr)
        return jax.tree.map(lambda x: shard(x, "param"), params), opt, losses

    def _metrics(losses, matched, mask, lr):
        return {
            "loss": masked_mean_loss(losses, mask),
            "lr": lr,
            "matched_frac": jnp.mean(matched.astype(jnp.float32)),
        }

    def pipelined_superstep(state: SwarmState, batch, perm, h_counts, rng,
                            mask=None):
        """Software-pipelined STEADY STATE (cfg.overlap; DESIGN.md
        §Pipeline). The payload of interaction t was packed/encoded at the
        end of superstep t-1 and rides in `state.inflight`; here its wire
        permute is dispatched BEFORE the local-step loop (no data dependence
        between the two, so latency-hiding scheduling can overlap them), the
        decode+average lands against the STALE packed model exactly as
        Algorithm 2 specifies, and the next payload is packed/encoded from
        the post-interaction model on the way out."""
        lr = lr_fn(state.step)
        S = state.params                       # superstep-start models
        infl = state.inflight
        assert infl is not None, \
            "overlap superstep needs a primed pipeline (pipeline_prologue)"
        codec = tr.codec
        layout = B.build_layout(S, block=codec.block)
        node_perm, pool_idx = tr.resolve_perm(perm)
        matched = node_perm != jnp.arange(cfg.n_nodes)
        if mask is not None:
            matched = matched & mask

        # 1. dispatch the in-flight payload's collective FIRST — one
        # permute per codec wire group (quantized) or the fp32 buffer
        payload = infl["wire"] if cfg.quantize else (infl["sbuf"],)
        recv = tr.permute_inflight(payload, perm)

        # 2. local steps — overlappable with the in-flight exchange
        params, opt, losses = run_local_steps(state, batch, h_counts, lr)

        # 3. land: decode+average against the STALE packed model S
        sbuf = infl["sbuf"]
        if cfg.quantize:
            m_rows = B.row_mask(matched, layout.rows_per_node)
            base_buf = codec.decode_avg(recv, sbuf, m_rows)
        else:
            base_buf = (sbuf + recv[0]) * 0.5
        # X_i <- (S_i + X_j')/2 + (X_i - S_i), flat: one pack of the
        # post-local-step model, combine in fp32 buffer space
        post_buf = B.pack(layout, params)
        m_col = matched[:, None]
        new_buf = jnp.where(m_col, base_buf + (post_buf - sbuf), post_buf)
        params = jax.tree.map(lambda x: shard(x, "param"),
                              B.unpack(layout, new_buf))
        if cfg.average_momentum and _has_leaves(opt):
            opt = jax.tree.map(lambda x: _avg(x, x[node_perm], matched), opt)

        # 4. refresh the packed comm copy + encode the NEXT payload. The
        # copy refreshes to the value SENT at this interaction (S, packed in
        # sbuf) — so the encode's sender-local distance proxy |new - prev|
        # is the one-superstep movement (gossip pull + local delta), a live
        # Γ sample, never the degenerate zero a post-model refresh would give
        if cfg.quantize:
            prev_buf = jnp.where(m_col, sbuf, infl["prev"])
            wire2 = codec.encode(new_buf, prev_buf, rng)
            new_infl = {"sbuf": new_buf, "prev": prev_buf, "wire": wire2}
        else:
            new_infl = {"sbuf": new_buf}

        metrics = _metrics(losses, matched, mask, lr)
        if cfg.track_potential:
            metrics["gamma"] = gamma_potential(params)
        return SwarmState(params, opt, None, state.step + 1,
                          new_infl), metrics

    def superstep(state: SwarmState, batch, perm, h_counts, rng, mask=None):
        lr = lr_fn(state.step)
        S = state.params                       # superstep-start models
        params, opt, losses = run_local_steps(state, batch, h_counts, lr)
        node_perm, _ = tr.resolve_perm(perm)
        matched = node_perm != jnp.arange(cfg.n_nodes)
        if mask is not None:
            matched = matched & mask

        new_residual = state.residual

        # compress_state: `state.prev` is the WIRE tuple of the comm copy
        # (encode_state in swarm_init) — decode it lazily to the packed
        # buffer the quantized exchange consumes as its distance proxy
        prev_buf = None
        if cs:
            layout = B.build_layout(S, block=tr.codec.block)
            prev_buf = tr.codec.decode_state(
                state.prev, (cfg.n_nodes, layout.n_padded))

        def exchange(tree, use_quant: bool):
            """Average each node's `tree` entry with its partner's through
            the transport (flat-buffer unless a *_legacy oracle routes
            per-leaf). `perm` carries the scalar pool index in
            ppermute_pool modes. Error-feedback codecs additionally thread
            the residual slot through the encode (closed over, since only
            one quantized exchange runs per superstep)."""
            nonlocal new_residual
            out = tr.mix_pair(tree, perm, matched, quantize=use_quant,
                              prev=(state.prev if use_quant and not cs
                                    else None),
                              prev_buf=prev_buf if use_quant else None,
                              rng=rng, mask=mask,
                              residual=state.residual if use_quant else None)
            if use_quant and ef:
                out, new_residual = out
            return out

        if cfg.nonblocking:
            # Algorithm 2: X_i <- (S_i + X_j') / 2 + (X_i - S_i), where the
            # partner contribution X_j' is its STALE comm copy (= S_j here:
            # the partner's current local delta is not yet visible).
            base = exchange(S, cfg.quantize)
            delta = jax.tree.map(lambda a, b: a.astype(jnp.float32) -
                                 b.astype(jnp.float32), params, S)
            params = jax.tree.map(
                lambda b, d, p: jnp.where(
                    matched.reshape((-1,) + (1,) * (p.ndim - 1)),
                    (b.astype(jnp.float32) + d).astype(p.dtype), p),
                base, delta, params)
        else:
            # Algorithm 1 (blocking): average the post-local-step models.
            params = exchange(params, cfg.quantize)

        if cfg.average_momentum and _has_leaves(opt):
            opt = jax.tree.map(lambda x: _avg(x, x[node_perm], matched), opt)

        params = jax.tree.map(lambda x: shard(x, "param"), params)
        new_prev = None
        if cs:
            # compressed refresh: re-encode the post-interaction model vs
            # zeros ONCE, then select wire ROWS by the matched mask —
            # unmatched nodes keep their old wire bytes untouched, so the
            # stored copy never re-quantizes (no error compounding)
            layout = B.build_layout(params, block=tr.codec.block)
            enc = tr.codec.encode_state(B.pack(layout, params),
                                        jax.random.fold_in(rng, 0x5E))
            m_rows = B.row_mask(matched, layout.rows_per_node)
            new_prev = tuple(jnp.where(m_rows, e, o)
                             for e, o in zip(enc, state.prev))
        elif state.prev is not None:
            # comm copy refreshes on interaction. Blocking: to the
            # post-interaction (averaged) model — the NEXT encode input is
            # H local steps away from it, so the quant distance proxy
            # |x - prev| stays live. Non-blocking: to S, the value
            # Algorithm 2 exchanged — the next encode input IS the
            # post-interaction model, so refreshing to it would collapse
            # the proxy to zero for matched nodes and wrap every decode.
            src = S if cfg.nonblocking else params
            new_prev = jax.tree.map(
                lambda pv, p: jnp.where(
                    matched.reshape((-1,) + (1,) * (p.ndim - 1)), p, pv),
                state.prev, src)

        metrics = _metrics(losses, matched, mask, lr)
        if cfg.track_potential:
            metrics["gamma"] = gamma_potential(params)
        return SwarmState(params, opt, new_prev, state.step + 1,
                          residual=new_residual), metrics

    return pipelined_superstep if cfg.overlap else superstep


def make_join_step(cfg: SwarmConfig):
    """Join bootstrap (elastic membership; DESIGN.md §Churn): returns
    `join_step(state, perm, join_mask) -> state`.

    A node joining mid-run must start from a live model, not its stale
    init — the scheduler emits an exclusive join bin (sched/bridge.py)
    whose `perm` swaps (joiner, donor) and whose `join_mask` marks the
    joiner. The bootstrap is ONE collective on the flat packed buffer
    (asserted on the jaxpr in tests/test_churn.py): pack the node-stacked
    params once, row-gather `buf[perm]` so the joiner's lane receives the
    donor's whole payload, select received rows at joiners only, unpack.
    Donor rows keep their packed values, so non-joiners round-trip
    bitwise (pack/unpack is exact — core/bucket.py).

    Codec state of the joiner is re-based: its comm copy `prev` becomes
    the bootstrapped model (the donor's — the value any later quantized
    encode should measure movement against) and its error-feedback
    residual is zeroed (it never transmitted anything). The optimizer
    state is left as initialized: the paper averages models only, and a
    joiner's momentum warm-up is local business. Not supported in the
    overlap pipeline (cfg.overlap) — the in-flight payload of the join
    bin would predate membership.
    """
    assert not cfg.overlap, \
        "join bootstrap needs the non-pipelined driver (overlap=False): " \
        "an in-flight payload packed before the join would go stale"
    assert not cfg.compress_state, \
        "join bootstrap re-bases the per-leaf comm copy; the wire-tuple " \
        "prev of compress_state is rejected at config time (registry)"
    codec = make_codec(cfg.codec, cfg.quant)

    def join_step(state: SwarmState, perm, join_mask):
        layout = B.build_layout(state.params, block=codec.block)
        buf = B.pack(layout, state.params)
        recv = buf[perm]                       # the one payload collective
        new_buf = jnp.where(join_mask[:, None], recv, buf)
        params = B.unpack(layout, new_buf)
        prev = state.prev
        if prev is not None:
            prev = jax.tree.map(
                lambda pv, p: jnp.where(
                    join_mask.reshape((-1,) + (1,) * (p.ndim - 1)), p, pv),
                prev, params)
        residual = state.residual
        if residual is not None:
            residual = jnp.where(join_mask[:, None], 0.0, residual)
        return SwarmState(params, state.opt, prev, state.step + 1,
                          state.inflight, residual)

    return join_step


def retire_nodes(state: SwarmState, left_mask) -> SwarmState:
    """Permanent-leave retirement (elastic membership; DESIGN.md §Churn).

    A left node's lane stays allocated (the SPMD shape is static) but must
    never contaminate the survivors: the scheduler guarantees it is never
    matched again (its mask rows are False forever), which already keeps
    it out of every matched-mean decode and out of SGP's (X, w) push mass
    — so params/opt/prev simply freeze in place. The one thing retired
    here is its error-feedback residual: zeroing it guarantees that even a
    buggy future re-match could not flush a ghost correction, and makes
    the post-leave state checkpoint-canonical (two runs that diverge only
    in WHEN they saved produce identical trees).
    """
    if state.residual is None:
        return state
    left_mask = jnp.asarray(left_mask)
    residual = jnp.where(left_mask[:, None], 0.0, state.residual)
    return SwarmState(state.params, state.opt, state.prev, state.step,
                      state.inflight, residual)


def make_mean_model_eval(loss_fn: Callable):
    """Evaluate the swarm's TRUE average model μ vs per-node models — the
    paper's §5 check ("the real average of all models is usually more
    accurate than an arbitrary model, but not significantly"). μ comes
    from checkpoint.mean_model_tree — the SAME code path the serving
    subsystem's checkpoint follower uses (serve/source.py), so --eval-mean
    and a served mean model can never silently diverge (bitwise-equal to
    the historical per-leaf mean; tests/test_serve.py)."""
    from repro.checkpoint import mean_model_tree

    @jax.jit
    def evaluate(params_stacked, batch_single):
        mu = mean_model_tree(params_stacked)
        loss_mu = loss_fn(mu, batch_single)
        loss_nodes = jax.vmap(lambda p: loss_fn(p, batch_single))(params_stacked)
        return {"loss_mean_model": loss_mu,
                "loss_node_mean": jnp.mean(loss_nodes),
                "loss_node_worst": jnp.max(loss_nodes)}
    return evaluate


def sample_h_counts(cfg: SwarmConfig, rng) -> "np.ndarray":  # noqa: F821
    """Host-side per-node local-step counts for this superstep."""
    import numpy as np
    if cfg.h_mode == "fixed":
        return np.full((cfg.n_nodes,), cfg.H, np.int32)
    if cfg.h_mode == "geometric":
        h = rng.geometric(1.0 / cfg.H, size=cfg.n_nodes)
        return np.clip(h, 1, cfg.h_max).astype(np.int32)
    raise ValueError(
        f"h_mode={cfg.h_mode!r}: per-node counts come from the scheduler "
        "bridge (sched/bridge.py engine_inputs), not from sampling")
