"""End-to-end training driver (CPU-runnable scales; same code path as the
production dry-run, minus the 512-device mesh).

  PYTHONPATH=src python -m repro.launch.train --arch transformer-wmt \
      --algo swarm --nodes 8 --steps 200 --reduced

Trains with SwarmSGD (or any baseline algorithm) on the synthetic LM
pipeline, logging loss / Γ potential / communication bytes, with periodic
checkpointing.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.algorithms import CAPABILITIES, make_algorithm, validate_run_config
from repro.algorithms.sgp import sgp_init_state
from repro.checkpoint import save_checkpoint
from repro.configs import get_config, reduced
from repro.core import (SwarmConfig, make_graph, sample_matching, swarm_init,
                        transport_from_config)
from repro.core.exchange import static_ppermute_matching  # noqa: F401
from repro.core.swarm import sample_h_counts
from repro.data import DataConfig, SyntheticLMDataset, make_node_batches
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import node_mesh
from repro.models import init_params, loss_fn as model_loss
from repro.optim import make_optimizer
from repro.quant.schemes import ModularQuantConfig


def build_trainer(cfg, algo: str, n_nodes: int, H: int, lr: float,
                  quantize: bool = False, nonblocking: bool = False,
                  graph_kind: str = "complete", seed: int = 0,
                  h_mode: str = "fixed", momentum: float = 0.9,
                  gossip_impl: str = None, pool_size: int = 8,
                  overlap: bool = False, h_max: int = 8,
                  quant: ModularQuantConfig = None,
                  rate_profile: str = "none", codec: str = None,
                  topology: str = None, compress_state: bool = False,
                  devices=None):
    """One construction path for EVERY algorithm (DESIGN.md §Baselines):
    validate the requested combination against the capability matrix,
    build ONE GossipTransport (whose wire codec comes from `codec`, the
    ``--codec`` spec — None follows the quant config = the q8 lattice),
    route all algorithms — swarm included — through make_algorithm with
    the uniform factory signature.

    `devices` (default: every device the process sees) sets the node
    mesh (launch/mesh.py node_mesh): one device vmaps every node on it;
    several hold one node each, and the returned state is placed so."""
    caps = validate_run_config(algo, gossip_impl=gossip_impl,
                               quantize=quantize, nonblocking=nonblocking,
                               overlap=overlap, rate_profile=rate_profile,
                               codec=codec, topology=topology,
                               compress_state=compress_state,
                               n_nodes=n_nodes)
    graph = make_graph(graph_kind, n_nodes)
    opt = make_optimizer("sgd", lr=lr, momentum=momentum,
                         state_dtype=cfg.opt_state_dtype)
    lf = lambda p, mb: model_loss(cfg, p, mb)  # noqa: E731
    lr_fn = lambda s: lr  # noqa: E731

    # engine-side config: H=1 algorithms (adpsgd/sgp/dpsgd/allreduce)
    # interact every step and consume exactly one batch slot; h-consuming
    # algorithms (swarm, localsgd) keep the variable h modes
    if caps.local_H:
        algo_H, algo_h_mode = H, h_mode
    else:
        algo_H, algo_h_mode = 1, "fixed"
    skw = dict(n_nodes=n_nodes, H=algo_H, h_mode=algo_h_mode, h_max=h_max,
               quantize=quantize,
               nonblocking=nonblocking or overlap, overlap=overlap,
               quant=quant or ModularQuantConfig(), pool_size=pool_size,
               compress_state=compress_state)
    if topology is not None:
        skw["topology"] = topology
    if codec is not None:
        skw["codec"] = codec
    if gossip_impl is not None:
        skw["gossip_impl"] = gossip_impl
    scfg = SwarmConfig(**skw)
    probe = jax.eval_shape(lambda k: init_params(k, cfg),
                           jax.random.PRNGKey(0))
    transport = transport_from_config(scfg, graph, seed, probe, devices)

    kw = dict(loss_fn=lf, opt_update=opt.update, lr_fn=lr_fn,
              n_nodes=n_nodes, transport=transport)
    if algo == "swarm":
        kw["scfg"] = scfg
    else:
        if algo == "localsgd":
            kw.update(H=H, h_max=scfg.h_loop_bound)
        if algo == "dpsgd":
            kw["graph"] = graph
        if caps.quantized:
            kw["quantize"] = quantize
        if "nonblocking" in caps.modes:
            kw["nonblocking"] = nonblocking
    step = make_algorithm(algo, **kw)

    rng = jax.random.PRNGKey(seed)
    state = swarm_init(rng, scfg, lambda k: init_params(k, cfg), opt.init)
    if algo == "sgp":
        state = sgp_init_state(state, n_nodes, quantize)
    state = place_nodes(state, node_mesh(n_nodes, devices))
    return jax.jit(step), state, scfg, graph


def place_nodes(tree, mesh):
    """Node-stacked leaves (leading [n_nodes] axis) one node per device of
    the node mesh, scalars replicated; on one device (mesh None) the
    leaves just become device arrays."""
    if mesh is None:
        return jax.tree.map(jnp.asarray, tree)
    node = NamedSharding(mesh, P("node"))
    repl = NamedSharding(mesh, P())
    return jax.device_put(
        tree, jax.tree.map(lambda x: node if np.ndim(x) else repl, tree))


def parse_straggler(spec: "str | None"):
    """--straggler FRAC:SLOWDOWN[:FAIL_RATE:FAIL_DURATION] -> StragglerConfig.
    e.g. "0.25:10" = slowest quarter of the nodes 10x slower;
    "0.25:10:0.01:5" additionally fails nodes at rate 0.01/unit-time for 5
    units (sched/clocks.py failure injection)."""
    from repro.sched import StragglerConfig
    if not spec:
        return StragglerConfig()
    parts = [float(x) for x in spec.split(":")]
    if len(parts) not in (2, 4):
        raise ValueError(f"--straggler {spec!r}: want FRAC:SLOWDOWN"
                         "[:FAIL_RATE:FAIL_DURATION]")
    kw = dict(fraction=parts[0], slowdown=parts[1])
    if len(parts) == 4:
        kw.update(fail_rate=parts[2], fail_duration=parts[3])
    return StragglerConfig(**kw)


def build_schedule(args, graph, scfg, caps=None):
    """--rate-profile plumbing: generate the event trace and compile it to
    a binned engine schedule (DESIGN.md §Sched). Returns (schedule, trace,
    clocks) — clocks is None for the synchronous uniform profile, whose
    trace reproduces the plain driver's matchings (and therefore its
    trajectory) bit-exactly on a complete graph. `caps` (the algorithm's
    capability row) drops the trace's local-step accrual to H=1 for the
    algorithms that interact every step (adpsgd/sgp/dpsgd/allreduce).
    With ``--avail`` (elastic membership, DESIGN.md §Churn) the clocks
    carry an AvailabilityModel and the schedule gains join/leave bins.
    Under a hierarchical topology (DESIGN.md §Hierarchy) the clocks run on
    the two-tier union graph with edge weights tuned so inter-group events
    land at ``inter_frac``; the per-event tier labels ride trace.meta and
    split the bins tier-pure so each bin prices on ONE link class."""
    from repro import sched as S
    from repro.core.hier import parse_topology
    topo = parse_topology(getattr(scfg, "topology", None), scfg.n_nodes)
    tseed = args.trace_seed if args.trace_seed is not None else args.seed
    H_eff = args.H if caps is None or caps.local_H else 1
    if scfg.gossip_impl not in ("gather", "gather_legacy"):
        raise ValueError(
            "--rate-profile drives the engine through arbitrary per-bin "
            "matchings, which only the gather transports accept from the "
            "driver; the ppermute/pool transports run heterogeneous traces "
            "via sched.bridge (pool_edges/static pairs restriction — see "
        "tests/test_sched_parity.py)")
    avail = None
    if getattr(args, "avail", None):
        if args.rate_profile in ("none", "uniform"):
            raise ValueError(
                "--avail rides the asynchronous Poisson clocks "
                "(join/leave events are quantized to clock rings) — use "
                "--rate-profile uniform_async or lognormal")
        avail = S.parse_avail(args.avail, args.nodes, tseed)
    if args.rate_profile == "uniform":
        if topo is not None and topo.n_groups > 1:
            raise ValueError(
                "--topology hier needs an asynchronous --rate-profile "
                "(uniform_async or lognormal): the synchronous uniform "
                "trace has no per-event tier coin, so inter-group "
                "exchanges would never fire")
        if graph.name != "complete" or graph.n % 2:
            # bit-exactness with the unscheduled driver needs every
            # sampled matching to be PERFECT (unmatched nodes still run
            # H local steps in the plain engine but accrue none in the
            # event model) — only complete graphs with even n guarantee
            # that. The schedule itself is still valid.
            print(json.dumps({"sched_warning":
                              "uniform profile is bit-exact with "
                              "--rate-profile none only on a complete "
                              f"graph with even n (got {graph.name}, "
                              f"n={graph.n})"}))
        rng = np.random.default_rng(tseed)
        trace = S.synchronous_trace(graph, args.steps, H=H_eff, rng=rng)
        # persist the matching stream's rng so a resumed run continues
        # the SAME matching sequence (sched_checkpoint_meta)
        trace.meta["matching_rng"] = rng.bit_generator.state
        clocks = None
    else:
        kind = "uniform" if args.rate_profile == "uniform_async" \
            else args.rate_profile
        profile = S.RateProfile(kind, sigma=args.rate_sigma)
        straggler = parse_straggler(args.straggler)
        event_graph, ew = graph, None
        if topo is not None and topo.n_groups > 1:
            # two-tier clocks: union graph carries both edge classes,
            # weighted so P(inter event) ≈ inter_frac (core/hier.py)
            event_graph, ew = topo.union_graph(), topo.edge_weights()
        clocks = S.PoissonClocks(event_graph,
                                 profile.make_rates(args.nodes, tseed),
                                 tseed, straggler, edge_weights=ew,
                                 avail=avail)
        n_events = args.steps * max(1, args.nodes // 2)
        trace = S.generate_trace(event_graph, profile, n_events, H=H_eff,
                                 h_max=scfg.h_max if H_eff > 1 else 1,
                                 h_mode="rate", seed=tseed, clocks=clocks)
    tiers = None
    if topo is not None and topo.n_groups > 1:
        tiers = topo.tier_of_pairs(trace.pairs)
        trace.meta["tiers"] = tiers
    return S.bin_trace(trace, tiers=tiers), trace, clocks


def sched_checkpoint_meta(args, trace, clocks) -> dict:
    """JSON-serializable scheduler state for checkpoint metadata: restoring
    `clocks` via PoissonClocks.from_state + `last_t` into generate_trace
    continues the exact event sequence (tests/test_sched.py)."""
    avail = clocks.avail if clocks is not None else None
    return {
        "profile": args.rate_profile,
        "rate_sigma": args.rate_sigma,
        "trace_seed": args.trace_seed if args.trace_seed is not None
        else args.seed,
        "straggler": args.straggler,
        "n_nodes": args.nodes,
        "n_events_done": int(trace.n_events),
        "clocks": clocks.state_dict() if clocks is not None else None,
        "last_t": trace.meta.get("last_t"),
        "matching_rng": trace.meta.get("matching_rng"),
        # elastic membership: the availability model embeds its own
        # intervals/phases, so resume needs neither the spec nor the
        # original trace file (sched/avail.py)
        "avail": avail.state_dict() if avail is not None else None,
    }


def restore_sched_clocks(meta: dict, graph):
    """Inverse of `sched_checkpoint_meta`: rebuild the event source from
    checkpoint metadata so a continued run generates the SAME sequence the
    uninterrupted run would have (bit-for-bit; asserted in
    tests/test_sched.py). Returns (clocks, last_t, matching_rng):
    asynchronous profiles get (PoissonClocks, last_t, None) — feed both to
    `generate_trace(..., clocks=..., last_t=...)`; the synchronous uniform
    profile gets (None, None, rng) — feed the rng to
    `synchronous_trace(..., rng=...)`."""
    from repro.sched import AvailabilityModel, PoissonClocks, RateProfile
    if meta.get("clocks") is None:
        rng = None
        if meta.get("matching_rng") is not None:
            rng = np.random.default_rng(int(meta["trace_seed"]))
            rng.bit_generator.state = meta["matching_rng"]
        return None, None, rng
    kind = "uniform" if meta["profile"] == "uniform_async" \
        else meta["profile"]
    profile = RateProfile(kind, sigma=meta.get("rate_sigma", 0.5))
    seed = int(meta["trace_seed"])
    rates = profile.make_rates(int(meta["n_nodes"]), seed)
    avail = AvailabilityModel.from_state(meta["avail"]) \
        if meta.get("avail") is not None else None
    clocks = PoissonClocks.from_state(
        meta["clocks"], graph, rates, seed,
        straggler=parse_straggler(meta.get("straggler")), avail=avail)
    last_t = np.asarray(meta["last_t"]) if meta.get("last_t") is not None \
        else None
    return clocks, last_t, None


# static_ppermute_matching is re-exported from repro.core.exchange (line
# ~28): THE static involution the ppermute transport compiles against,
# shared by transport_from_config (which bakes it into the collective) and
# sample_gossip_perm below (which must feed the engine the same matching,
# or the matched mask would disagree with the actual data movement).


def sample_gossip_perm(scfg: SwarmConfig, graph, rng_np,
                       seed: int = 0, topo=None) -> "np.ndarray":
    """Per-superstep `perm` input: a fresh matching for the gather modes,
    the scalar pool index (broadcast [n_nodes]) that ppermute_pool's
    lax.switch consumes, or — for the plain ppermute modes, whose pairs are
    compiled in — the one static matching baked at build time (`seed` must
    match the build_trainer seed). A `topo` (core/hier.py HierTopology)
    re-routes the draw through the tier coin: `sample_event` /
    `sample_pool_index` flip inter w.p. inter_frac, and DEGENERATE to this
    function's flat draws bit-for-bit when n_groups == 1 (the G = n
    contract, tests/test_hier.py)."""
    impl = scfg.gossip_impl
    if topo is not None:
        if impl.startswith("ppermute_pool"):
            idx, _tier = topo.sample_pool_index(rng_np, scfg.pool_size)
            return np.full((scfg.n_nodes,), idx, np.int32)
        if impl.startswith("ppermute"):
            raise ValueError(
                "hier topology cannot ride the single static ppermute "
                "matching (one compiled matching carries one tier) — use "
                "gather or ppermute_pool")
        perm, _tier = topo.sample_event(rng_np)
        return perm
    if impl.startswith("ppermute_pool"):
        idx = int(rng_np.integers(scfg.pool_size))
        return np.full((scfg.n_nodes,), idx, np.int32)
    if impl.startswith("ppermute"):
        return static_ppermute_matching(graph, seed)
    return sample_matching(graph, rng_np)


def presample_inputs(scfg: SwarmConfig, graph, rng_np, seed: int,
                     n_steps: int, uses_matching: bool = True, topo=None):
    """Host-side presample of the whole run's (perm, h) streams as stacked
    [n_steps, n_nodes] int32 arrays. Consumes `rng_np` in EXACTLY the
    per-superstep order the old loop drew (perm, then h, step by step), so
    the stream — and therefore the trajectory — is bitwise the one the
    per-step sampling produced. Ship the result to the device once
    (jnp.asarray) and index rows device-side: the steady-state loop then
    makes zero host->device transfers (ROADMAP item 5; the scan driver
    slices whole chunks out of the same arrays)."""
    perms = np.empty((n_steps, scfg.n_nodes), np.int32)
    hs = np.empty((n_steps, scfg.n_nodes), np.int32)
    for t in range(n_steps):
        perms[t] = (sample_gossip_perm(scfg, graph, rng_np, seed, topo)
                    if uses_matching else sample_matching(graph, rng_np))
        hs[t] = sample_h_counts(scfg, rng_np)
    return perms, hs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="transformer-wmt")
    ap.add_argument("--algo", default="swarm",
                    choices=["swarm", "allreduce", "localsgd", "dpsgd",
                             "adpsgd", "sgp"])
    ap.add_argument("--nodes", type=int, default=8)
    ap.add_argument("--H", type=int, default=2)
    ap.add_argument("--h-mode", default="fixed", choices=["fixed", "geometric"])
    ap.add_argument("--h-max", type=int, default=8,
                    help="static local-step loop bound for variable h modes "
                         "(geometric sampling / scheduler traces)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4, help="per node per local step")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--quantize", action="store_true")
    ap.add_argument("--codec", default=os.environ.get("REPRO_CODEC") or None,
                    help="wire codec for --quantize (DESIGN.md §Codec): "
                         "q2..q16 (modular lattice — q4 and below pack two "
                         "codes per byte, q9+ widen to a uint16 wire), bf16 "
                         "(straight cast), topk:<frac> (per-row top-k + "
                         "error feedback, e.g. topk:0.25). Default: the q8 "
                         "lattice at the quant config's bit width. Env "
                         "default: REPRO_CODEC")
    ap.add_argument("--nonblocking", action="store_true")
    ap.add_argument("--overlap", action="store_true",
                    help="pipelined non-blocking superstep: dispatch the "
                         "in-flight payload's collective before the local "
                         "steps (implies --nonblocking; DESIGN.md §Pipeline)")
    ap.add_argument("--gossip-impl", "--gossip_impl", default=None,
                    choices=["gather", "ppermute", "ppermute_pool",
                             "gather_legacy", "ppermute_legacy",
                             "ppermute_pool_legacy"],
                    help="gossip transport (default: SwarmConfig default, "
                         "i.e. the flat-buffer gather)")
    ap.add_argument("--pool-size", "--pool_size", type=int, default=8,
                    help="K precompiled matchings for the ppermute_pool "
                         "lax.switch transport")
    ap.add_argument("--topology", default=os.environ.get("REPRO_TOPOLOGY")
                    or None,
                    help="node-axis topology (DESIGN.md §Hierarchy): "
                         "'hier:G[:inter_frac]' shards the swarm into "
                         "groups of G nodes — gossip is intra-group except "
                         "an inter_frac (default 0.25) slice of events "
                         "that exchange one lane-aligned cross-group "
                         "matching, priced on the slow DCN tier. 'flat' / "
                         "unset = the complete single-tier swarm. "
                         "'hier:G' with G = nodes is bitwise the flat "
                         "path. Env default: REPRO_TOPOLOGY")
    ap.add_argument("--compress-state", "--compress_state",
                    action="store_true",
                    help="keep the quantized comm copy codec-encoded at "
                         "rest (core/swarm.py compress_state): the prev "
                         "buffer lives as lattice wire words, decoded "
                         "lazily inside the exchange — ~4x less resident "
                         "state per node for q8. Requires --quantize with "
                         "a lattice codec; blocking mode only")
    ap.add_argument("--graph", default="complete")
    # validate the env-provided default HERE: argparse only checks values
    # given on the command line, so a typo'd REPRO_RATE_PROFILE would
    # otherwise surface as a confusing failure deep inside RateProfile
    rate_profiles = ["none", "uniform", "uniform_async", "lognormal"]
    env_profile = os.environ.get("REPRO_RATE_PROFILE", "none")
    if env_profile not in rate_profiles:
        ap.error(f"REPRO_RATE_PROFILE={env_profile!r}: choose from "
                 f"{rate_profiles}")
    ap.add_argument("--rate-profile", "--rate_profile",
                    default=env_profile, choices=rate_profiles,
                    help="drive training from a discrete-event scheduler "
                         "trace (sched/; DESIGN.md §Sched): per-node "
                         "Poisson clocks at uniform_async/lognormal rates "
                         "binned into masked supersteps. 'uniform' is the "
                         "synchronous idealization (bit-exact with 'none' "
                         "on a complete graph). Env default: "
                         "REPRO_RATE_PROFILE")
    ap.add_argument("--rate-sigma", type=float, default=0.5,
                    help="lognormal rate-profile shape")
    ap.add_argument("--avail", default=os.environ.get("REPRO_AVAIL_PROFILE")
                    or None,
                    help="elastic-membership availability profile "
                         "(sched/avail.py; DESIGN.md §Churn): "
                         "'day_night:period=P,duty=D[,join=F:T0:T1]"
                         "[,leave=F:T0:T1][,seed=S]' gives every node a "
                         "phase-shifted day/night duty cycle with optional "
                         "late joiners and permanent leavers; 'trace:FILE' "
                         "reads per-node uptime intervals from a file. "
                         "Needs an asynchronous --rate-profile and the "
                         "per-step driver. Env default: REPRO_AVAIL_PROFILE")
    ap.add_argument("--straggler", default=None,
                    help="FRAC:SLOWDOWN[:FAIL_RATE:FAIL_DURATION] straggler "
                         "and transient-failure injection, e.g. 0.25:10")
    ap.add_argument("--trace-seed", type=int, default=None,
                    help="scheduler clock seed (default: --seed)")
    ap.add_argument("--non-iid", type=float, default=None,
                    help="Dirichlet alpha for per-node data skew")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale variant of the arch")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--scan-chunk", "--scan_chunk", type=int,
                    default=int(os.environ.get("REPRO_SCAN_CHUNK", "0")),
                    help="fuse K supersteps per dispatch in a donated "
                         "lax.scan (core/scan.py; DESIGN.md §Fusion). 0 = "
                         "per-step driver. Bitwise identical to the "
                         "per-step driver; chunk boundaries are the "
                         "checkpointable points. Env default: "
                         "REPRO_SCAN_CHUNK")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--eval-mean", action="store_true",
                    help="also evaluate the true average model μ (paper §5)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="checkpoint every N steps into --ckpt (treated as "
                         "a DIRECTORY of step-stamped checkpoints — the "
                         "layout repro.serve's CheckpointFollower polls). "
                         "The per-step driver lands them every N steps; "
                         "the scan driver at the chunk boundaries that "
                         "cross a multiple of N (the checkpointable "
                         "points). 0 = one final checkpoint at --ckpt")
    ap.add_argument("--out", default=None, help="json metrics path")
    args = ap.parse_args()
    use_compile_cache()
    # --eval-mean composes with the scan driver: the intermediate states
    # are consumed inside the fused scan, so μ is evaluated at CHUNK
    # BOUNDARIES (the checkpointable points) instead of per logged step —
    # bitwise the per-step driver's value at the same step, since the
    # drivers themselves are bitwise identical (tests/test_scan_driver.py)
    if args.avail:
        if args.rate_profile in ("none", "uniform"):
            ap.error("--avail rides the asynchronous Poisson clocks; use "
                     "--rate-profile uniform_async or lognormal")
        if args.scan_chunk:
            ap.error("--avail schedules contain join bins, which branch "
                     "per superstep (join-bootstrap vs gossip) — the fused "
                     "scan driver replays gossip bins only; drop "
                     "--scan-chunk (DESIGN.md §Churn)")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg, n_layers=args.layers, d_model=args.d_model)
    ds = SyntheticLMDataset(
        DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                   seed=args.seed, non_iid_alpha=args.non_iid),
        n_nodes=args.nodes)

    sched_on = args.rate_profile != "none"
    # per-algorithm capability matrix (DESIGN.md §Baselines): every
    # algorithm that supports it runs under the scheduler bridge; the
    # unsupported combinations fail HERE, at config time, with the matrix
    # row in the error message
    caps = validate_run_config(
        args.algo, gossip_impl=args.gossip_impl, quantize=args.quantize,
        nonblocking=args.nonblocking, overlap=args.overlap,
        rate_profile=args.rate_profile, codec=args.codec, avail=args.avail,
        topology=args.topology, compress_state=args.compress_state,
        n_nodes=args.nodes)
    h_mode = args.h_mode
    if sched_on and args.rate_profile != "uniform" and caps.local_H:
        h_mode = "trace"           # per-node counts come from the bridge
    step, state, scfg, graph = build_trainer(
        cfg, args.algo, args.nodes, args.H, args.lr, args.quantize,
        args.nonblocking, args.graph, args.seed, h_mode,
        gossip_impl=args.gossip_impl, pool_size=args.pool_size,
        overlap=args.overlap, h_max=args.h_max,
        rate_profile=args.rate_profile, codec=args.codec,
        topology=args.topology, compress_state=args.compress_state)
    from repro.core.hier import parse_topology
    topo = parse_topology(args.topology, args.nodes)
    rng_np = np.random.default_rng(args.seed)
    key = jax.random.PRNGKey(args.seed + 1)
    h_max = scfg.h_loop_bound
    mesh = node_mesh(args.nodes)

    schedule = trace = clocks = None
    n_steps = args.steps
    if sched_on:
        from repro.sched import trace_stats
        schedule, trace, clocks = build_schedule(args, graph, scfg, caps)
        n_steps = schedule.n_supersteps
        print(json.dumps({"sched": {
            "profile": args.rate_profile, "n_events": trace.n_events,
            "n_supersteps": n_steps, "density": schedule.density(),
            **{k: v for k, v in trace_stats(trace).items()
               if not isinstance(v, list)}}}))

    history = []
    t0 = time.time()

    def write_ckpt(path, ck_state, step_no):
        """One checkpoint-writing path for final and periodic saves; meta
        carries the swarm width (serving followers validate it) and the
        step the save landed at."""
        meta = {"arch": cfg.name, "algo": args.algo, "steps": args.steps,
                "nodes": args.nodes, "step": step_no}
        if sched_on:
            meta["sched"] = sched_checkpoint_meta(args, trace, clocks)
        if args.quantize:
            # persist the codec state (comm copy + error-feedback residual)
            # alongside the params so a resumed quantized run continues
            # the encode sequence bit-exactly (tests/test_codecs.py). A
            # pipelined run drains FIRST: in overlap mode the comm copy
            # lives packed in state.inflight, and the epilogue unpacks it
            # back into prev so the checkpoint carries a LIVE scale proxy
            # (on a COPY — the training state itself keeps flowing)
            from repro.core.swarm import codec_checkpoint_tree
            if scfg.overlap:
                from repro.core import pipeline_epilogue
                ck_state = pipeline_epilogue(scfg, ck_state)
            tree = codec_checkpoint_tree(ck_state)
            # compress_state changes the SHAPE of the saved `prev` (codec
            # wire tuple vs dense stacked tree) — followers need the flag
            # to build the right template (serve/source.py)
            meta["codec"] = {"spec": args.codec or "q8",
                             "state": sorted(tree),
                             "compress_state": bool(scfg.compress_state)}
            save_checkpoint(path, jax.device_get(tree), meta)
        else:
            save_checkpoint(path, jax.device_get(ck_state.params), meta)

    def periodic_ckpt(step_no):
        os.makedirs(args.ckpt, exist_ok=True)
        path = os.path.join(args.ckpt, f"step_{step_no:06d}")
        write_ckpt(path, state, step_no)

    # satellite of ROADMAP item 5: presample the WHOLE schedule host-side
    # and ship it once — the steady-state loop (either driver) reads
    # device-resident rows, zero host->device transfers per superstep
    churn = sched_on and schedule.kinds is not None
    if sched_on:
        from repro.sched import stacked_engine_inputs
        if churn:
            # churn schedules mix gossip and join bins, which
            # stacked_engine_inputs rejects; the gather transport takes the
            # schedule's own rows verbatim (join bins branch in the loop)
            perms_np, hs_np, mask_np = (schedule.perms, schedule.h,
                                        schedule.mask)
        else:
            perms_np, hs_np, mask_np = stacked_engine_inputs(
                schedule, 0, n_steps, scfg.gossip_impl)
    else:
        perms_np, hs_np = presample_inputs(scfg, graph, rng_np, args.seed,
                                           n_steps, caps.uses_matching,
                                           topo=topo)
        mask_np = None
    # pre-split into per-step / per-chunk device arrays HERE, not in the
    # loop: indexing a stacked device array with a fresh python int is a
    # new static gather each time — a jit-cache miss and recompile per
    # superstep that costs ~1000x the dispatch it feeds
    if args.scan_chunk > 0:
        # scan driver (core/scan.py): K supersteps per dispatch, donated
        # (state, key) carry — bitwise identical to the per-step branch
        # below; chunk boundaries are the checkpointable points
        from repro.core.scan import make_superstep_scan
        chunk_fn = make_superstep_scan(step, with_mask=sched_on)
        ev = None
        if args.eval_mean:
            from repro.core.swarm import make_mean_model_eval
            from repro.models import loss_fn as mlf
            ev = make_mean_model_eval(lambda p, b: mlf(cfg, p, b))
        starts = list(range(0, n_steps, args.scan_chunk))
        perm_cks = [jnp.asarray(perms_np[t:t + args.scan_chunk])
                    for t in starts]
        h_cks = [jnp.asarray(hs_np[t:t + args.scan_chunk]) for t in starts]
        mask_cks = [jnp.asarray(mask_np[t:t + args.scan_chunk])
                    for t in starts] if sched_on else None
        for c, t in enumerate(starts):
            K = min(args.scan_chunk, n_steps - t)
            nbs = [make_node_batches(ds, s, args.batch * h_max)
                   for s in range(t, t + K)]
            batch = {k: jnp.asarray(np.stack(
                [nb[k].reshape(args.nodes, h_max, args.batch, args.seq)
                 for nb in nbs])) for k in nbs[0]}
            cargs = (state, key, batch, perm_cks[c], h_cks[c])
            if sched_on:
                cargs += (mask_cks[c],)
            state, key, ms = chunk_fn(*cargs)
            ms = jax.device_get(ms)
            em = None
            if ev is not None:
                # μ evaluation at the chunk boundary: the scan consumes the
                # intermediate states, so the boundary (= checkpointable
                # point) is where the mean model exists to evaluate — same
                # batch slice the per-step driver would use at this step
                nb_last = nbs[-1]
                eb = {"tokens": jnp.asarray(
                          nb_last["tokens"][0].reshape(-1, args.seq)),
                      "targets": jnp.asarray(
                          nb_last["targets"][0].reshape(-1, args.seq))}
                if args.algo == "sgp":
                    from repro.algorithms.sgp import sgp_debias
                    em = ev(sgp_debias(state.params), eb)
                else:
                    em = ev(state.params, eb)
                em = {k: float(v) for k, v in em.items()}
            for i in range(K):
                s = t + i
                boundary = em is not None and i == K - 1
                if s % args.log_every == 0 or s == n_steps - 1 or boundary:
                    rec = {"step": s, "loss": float(ms["loss"][i]),
                           "gamma": float(ms["gamma"][i])
                           if "gamma" in ms else 0.0,
                           "wall_s": round(time.time() - t0, 1)}
                    if boundary:
                        rec.update(em)
                    history.append(rec)
                    print(json.dumps(rec))
            if args.ckpt and args.ckpt_every and \
                    (t + K) // args.ckpt_every > t // args.ckpt_every:
                periodic_ckpt(t + K)
    else:
        perm_rows = [jnp.asarray(p) for p in perms_np]
        h_rows = [jnp.asarray(h) for h in hs_np]
        mask_rows = [jnp.asarray(m) for m in mask_np] if sched_on else None
        join_fn = None
        if churn:
            from repro.core import make_join_step, retire_nodes
            from repro.sched import EVENT_JOIN
            join_fn = jax.jit(make_join_step(scfg))
        for t in range(n_steps):
            if churn and schedule.retire[t].any():
                # permanent leaves taking effect before this bin: retire
                # the nodes' codec state (their params stay frozen — the
                # mask already never selects them again)
                state = retire_nodes(state, jnp.asarray(schedule.retire[t]))
            if churn and schedule.kinds[t] == EVENT_JOIN:
                # exclusive join bin: bootstrap the joiner from the donor's
                # packed payload — one collective, no batch, no rng
                state = join_fn(state, perm_rows[t], mask_rows[t])
                joiner = int(np.nonzero(schedule.mask[t])[0][0])
                rec = {"step": t, "event": "join", "joiner": joiner,
                       "donor": int(schedule.perms[t][joiner]),
                       "wall_s": round(time.time() - t0, 1)}
                history.append(rec)
                print(json.dumps(rec))
                continue
            nb = make_node_batches(ds, t, args.batch * h_max)
            batch = place_nodes({k: v.reshape(args.nodes, h_max, args.batch,
                                              args.seq)
                                 for k, v in nb.items()}, mesh)
            perm, h = perm_rows[t], h_rows[t]
            mask = mask_rows[t] if sched_on else None
            key, sub = jax.random.split(key)
            state, m = (step(state, batch, perm, h, sub, mask) if sched_on
                        else step(state, batch, perm, h, sub))
            if t % args.log_every == 0 or t == n_steps - 1:
                rec = {"step": t, "loss": float(m["loss"]),
                       "gamma": float(m.get("gamma", 0.0)),
                       "wall_s": round(time.time() - t0, 1)}
                if args.eval_mean:
                    from repro.core.swarm import make_mean_model_eval
                    from repro.models import loss_fn as mlf
                    ev = make_mean_model_eval(lambda p, b: mlf(cfg, p, b))
                    eb = {"tokens": jnp.asarray(nb["tokens"][0].reshape(-1, args.seq)),
                          "targets": jnp.asarray(nb["targets"][0].reshape(-1, args.seq))}
                    if args.algo == "sgp":
                        # the push-sum payload evaluates at the de-biased X/w
                        from repro.algorithms.sgp import sgp_debias
                        em = ev(sgp_debias(state.params), eb)
                    else:
                        em = ev(state.params, eb)
                    rec.update({k: float(v) for k, v in em.items()})
                history.append(rec)
                print(json.dumps(rec))
            if args.ckpt and args.ckpt_every and \
                    (t + 1) % args.ckpt_every == 0:
                periodic_ckpt(t + 1)
        if churn and schedule.retire[n_steps].any():
            from repro.core import retire_nodes
            state = retire_nodes(state, jnp.asarray(schedule.retire[n_steps]))
    predicted = None
    if sched_on:
        # price the trace end-to-end with the wall-clock cost model —
        # the predicted multi-node time for this (algo, arch, transport,
        # quant, rate profile) configuration (DESIGN.md §Sched). Pairwise
        # algorithms (swarm/adpsgd/sgp) replay per event; bulk-synchronous
        # baselines (localsgd/dpsgd/allreduce) pay a global rendezvous +
        # collective per bridge bin
        from repro.sched import (bsp_payload_factor, cost_params_from_model,
                                 predict_all_modes, predict_bsp_walltime)
        cp = cost_params_from_model(cfg, seq_len=args.seq,
                                    local_batch=args.batch,
                                    quantize=args.quantize,
                                    codec=args.codec,
                                    topology=args.topology)
        if caps.pricing == "pairwise":
            predicted = predict_all_modes(trace, cp,
                                          tiers=trace.meta.get("tiers"))
        else:
            predicted = predict_bsp_walltime(
                trace, schedule, cp,
                payload_factor=bsp_payload_factor(args.algo, graph))
        print(json.dumps({"sched_cost": predicted}))
        if trace.meta.get("tiers") is not None \
                and isinstance(predicted.get("blocking"), dict):
            # per-tier link utilization at a glance (the full per-mode
            # breakdown is inside sched_cost["<mode>"]["tiers"])
            print(json.dumps({"link_util": {
                "topology": args.topology,
                **predicted["blocking"]["tiers"]}}))
    if args.ckpt:
        if args.ckpt_every:
            path = os.path.join(args.ckpt, f"step_{n_steps:06d}")
            periodic_ckpt(n_steps)
        else:
            path = args.ckpt
            write_ckpt(path, state, n_steps)
        print("checkpoint ->", path)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"args": vars(args), "history": history,
                       "sched_cost": predicted}, f, indent=1)


if __name__ == "__main__":
    main()
