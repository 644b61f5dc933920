"""Benchmark harness — one table per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only t1,t4]

Prints ``name,us_per_call,derived`` CSV lines plus JSON artifacts under
results/bench/. Paper mapping:
  t1_convergence   — Table 1 / Fig 1: Swarm vs baselines, equal step budget
  t2_localsteps    — Fig 2(a)/6(b): local-step count H ablation
  t3_quantization  — Fig 8: 8-bit quantized gossip vs fp32
  t4_comm_cost     — Fig 2(b)/4: per-superstep communication bytes vs nodes
                     (analytic curves + ACTUAL packed flat-buffer payload)
  t5_potential     — Lemma F.3: Γ_t vs the analytic bound (exact simulator)
  t6_nonblocking   — Extension 2: stale vs blocking averaging
  t7_roofline      — §Roofline: dry-run table (reads results/dryrun/*.json)
  t8_transport     — DESIGN.md §Perf: flat-buffer vs per-leaf legacy gossip
                     microbench (exact + quantized), compile + steady-state
  t9_async         — DESIGN.md §Pipeline: blocking vs overlapped
                     (double-buffered) non-blocking superstep, quantized
                     ppermute_pool transport
  t10_sched        — DESIGN.md §Sched: discrete-event scheduler —
                     predicted vs simulated wall-clock per rate profile,
                     bridged-engine training on heterogeneous traces,
                     uniform profile bit-exact vs the plain engine
  t11_baselines    — DESIGN.md §Baselines: every algorithm on the unified
                     exchange layer under one lognormal profile, fp32+q8,
                     predicted-vs-simulated wall-clock per pricing family
  t12_codecs       — DESIGN.md §Codec: swarm + AD-PSGD × {fp32, q8, q4,
                     topk} — measured packed wire bytes per codec
                     (asserted == declared WireLayout) + codec-priced
                     predicted-vs-simulated wall-clock
  t13_fused        — DESIGN.md §Fusion: scan-driven superstep vs the
                     per-step driver — un-blocked host dispatch cost per
                     superstep (fp32 + q8), paired interleaved rounds,
                     compile time; acceptance: scan >= 5x lower
  t14_churn        — DESIGN.md §Churn: day/night availability — churn
                     trace (joins + leaves) through the bridged engine's
                     retire/join/masked-superstep loop, kind-aware
                     predicted-vs-simulated wall-clock
  t15_serve        — DESIGN.md §Serving: continuous-batching engine under
                     open-loop Poisson arrivals with a swarm model landing
                     mid-run — tokens/s, p50/p99 token latency, queue
                     depth, time-to-fresh-model; asserts >=1 hot swap,
                     0 dropped in-flight, 0 decode recompiles
  t16_hier         — DESIGN.md §Hierarchy: flat vs two-tier hier gossip at
                     equal node count — trajectory quality, step time,
                     per-tier payload bytes/seconds from the tiered cost
                     model, q8-compressed resident comm copy (>= 2x), and
                     the 1024-node/512-device dry-run lowering
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, "src")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from benchmarks.common import (BenchSetup, comm_bytes_per_superstep,  # noqa: E402
                               run_steps)

OUT = "results/bench"


def emit(name, us, derived):
    print(f"{name},{us:.1f},{derived}")


def save(name, obj):
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, name + ".json"), "w") as f:
        json.dump(obj, f, indent=1)


def t1_convergence(quick=False):
    steps = 25 if quick else 80
    setup = BenchSetup()
    out = {}
    for algo in ["swarm", "allreduce", "localsgd", "dpsgd", "adpsgd", "sgp"]:
        r = run_steps(setup, algo, steps)
        out[algo] = r
        emit(f"t1_convergence/{algo}", r["us_per_step"],
             f"final_loss={np.mean(r['loss'][-5:]):.4f}")
    save("t1_convergence", {k: {"loss": v["loss"]} for k, v in out.items()})
    return out


def t2_localsteps(quick=False):
    steps = 25 if quick else 80
    out = {}
    for H in ([1, 4] if quick else [1, 2, 4, 8]):
        r = run_steps(BenchSetup(H=H), "swarm", steps)
        out[H] = r
        emit(f"t2_localsteps/H{H}", r["us_per_step"],
             f"final_loss={np.mean(r['loss'][-5:]):.4f};"
             f"gamma={np.mean(r['gamma'][-5:]):.4g}")
    save("t2_localsteps", {str(k): {"loss": v["loss"], "gamma": v["gamma"]}
                           for k, v in out.items()})
    return out


def t3_quantization(quick=False):
    steps = 25 if quick else 80
    out = {}
    for name, kw in [("fp32", {}), ("q8", dict(quantize=True))]:
        r = run_steps(BenchSetup(), "swarm", steps, **kw)
        b = comm_bytes_per_superstep("swarm", 8, r["n_params"], 2,
                                     quantize=(name == "q8"))
        out[name] = {**r, "bytes_per_superstep": b}
        emit(f"t3_quantization/{name}", r["us_per_step"],
             f"final_loss={np.mean(r['loss'][-5:]):.4f};bytes={b:.4g}")
    ratio = out["fp32"]["bytes_per_superstep"] / out["q8"]["bytes_per_superstep"]
    emit("t3_quantization/compression", 0.0, f"wire_ratio={ratio:.2f}x")
    save("t3_quantization", {k: {"loss": v["loss"],
                                 "bytes": v["bytes_per_superstep"]}
                             for k, v in out.items()})
    return out


def t4_comm_cost(quick=False):
    """Analytic per-node wire bytes per superstep (the paper's Fig. 4 shape:
    Swarm flat & lowest as node count grows; D-PSGD & AllReduce highest),
    plus the ACTUAL packed flat-buffer payload of the bench model — the
    quantized wire saving is measured from real (q, scales) arrays, not
    assumed from the formula."""
    from benchmarks.common import measured_payload
    n_params = 11_000_000  # ResNet18-scale, matching the paper's figure
    out = {}
    for n in [8, 16, 32, 64, 128]:
        row = {a: comm_bytes_per_superstep(a, n, n_params, H=2)
               for a in ["swarm", "allreduce", "localsgd", "dpsgd", "adpsgd",
                         "sgp"]}
        row["swarm_q8"] = comm_bytes_per_superstep("swarm", n, n_params, H=2,
                                                   quantize=True)
        out[n] = row
        emit(f"t4_comm_cost/n{n}", 0.0,
             ";".join(f"{k}={v / 1e6:.1f}MB" for k, v in row.items()))
    mp = measured_payload()
    # byte truthfulness: EVERY codec's declared WireLayout == real arrays
    for key in [k[:-len("_payload_bytes")] for k in mp
                if k.endswith("_payload_bytes")]:
        assert mp[f"{key}_payload_bytes"] == mp[f"{key}_formula_bytes"], key
    ratio = mp["fp32_payload_bytes"] / mp["q8_payload_bytes"]
    out["measured"] = {**mp, "wire_ratio": ratio}
    emit("t4_comm_cost/measured", 0.0,
         f"fp32={mp['fp32_payload_bytes']}B;q8={mp['q8_payload_bytes']}B;"
         f"wire_ratio={ratio:.2f}x;pad_overhead="
         f"{mp['n_padded'] / mp['n_coords'] - 1:.2%}")
    codec_bytes = {k[:-len("_payload_bytes")]: v for k, v in mp.items()
                   if k.endswith("_payload_bytes")}
    emit("t4_comm_cost/per_codec", 0.0,
         ";".join(f"{k}={v}B" for k, v in sorted(codec_bytes.items())))
    save("t4_comm_cost", out)
    return out


def t5_potential(quick=False):
    from repro.core.graph import make_graph
    from repro.core.potential import gamma_bound
    from repro.core.simulator import (SimConfig, quadratic_problem,
                                      run_simulation)
    T = 1500 if quick else 4000
    out = {}
    for graph_kind in ["complete", "hypercube", "ring"]:
        for H in [1, 2, 4]:
            g = make_graph(graph_kind, 16)
            grad_fn, loss_fn, gom, _ = quadratic_problem(16, 16, noise=0.1,
                                                         hetero=0.2)
            x0 = np.tile(np.random.default_rng(0).normal(size=(1, 16)),
                         (16, 1))
            tr = run_simulation(g, x0, grad_fn,
                                SimConfig(H=H, eta=0.02, seed=0), T,
                                record_every=20)
            measured = float(np.mean(tr.gamma[len(tr.gamma) // 2:]))
            bound = gamma_bound(16, g.r, g.lambda2, 0.02, H, 25.0)
            key = f"{graph_kind}/H{H}"
            out[key] = {"gamma": measured, "bound": bound,
                        "lambda2": g.lambda2, "r": g.r}
            emit(f"t5_potential/{key}", 0.0,
                 f"gamma={measured:.4g};lemmaF3_bound={bound:.4g};"
                 f"ok={measured < bound}")
    save("t5_potential", out)
    return out


def t6_nonblocking(quick=False):
    steps = 25 if quick else 80
    out = {}
    for name, kw in [("blocking", {}),
                     ("nonblocking", dict(nonblocking=True)),
                     ("nb_geomH", dict(nonblocking=True,
                                       h_mode="geometric"))]:
        r = run_steps(BenchSetup(), "swarm", steps, **kw)
        out[name] = r
        emit(f"t6_nonblocking/{name}", r["us_per_step"],
             f"final_loss={np.mean(r['loss'][-5:]):.4f}")
    save("t6_nonblocking", {k: {"loss": v["loss"]} for k, v in out.items()})
    return out


def t7_roofline(quick=False):
    import glob
    rows = []
    for path in sorted(glob.glob("results/dryrun/*.json")):
        with open(path) as f:
            r = json.load(f)
        if "error" in r or "skipped" in r:
            continue
        rows.append(r)
        emit(f"t7_roofline/{r['arch']}__{r['shape']}__{r['mesh']}",
             r.get("t_compile_s", 0) * 1e6,
             f"bottleneck={r.get('bottleneck')};"
             f"compute_s={r.get('compute_s', 0):.4g};"
             f"memory_s={r.get('memory_s', 0):.4g};"
             f"collective_s={r.get('collective_s', 0):.4g}")
    if not rows:
        emit("t7_roofline/none", 0.0, "run repro.launch.sweep first")
    save("t7_roofline_rows", {"n": len(rows)})
    return rows


def t8_topology(quick=False):
    """Theory's (r²/λ₂²+1) factor at the SPMD level: swarm training on
    different interaction graphs — Γ ordering must follow mixing quality."""
    steps = 20 if quick else 50
    out = {}
    for graph in ["complete", "hypercube", "ring", "hierarchical"]:
        r = run_steps(BenchSetup(n_nodes=16, graph=graph), "swarm", steps)
        out[graph] = r
        emit(f"t8_topology/{graph}", r["us_per_step"],
             f"final_loss={np.mean(r['loss'][-5:]):.4f};"
             f"gamma={np.mean(r['gamma'][-5:]):.4g}")
    save("t8_topology", {k: {"loss": v["loss"], "gamma": v["gamma"]}
                         for k, v in out.items()})
    return out


def t9_node_scaling(quick=False):
    """Paper Fig 6(a): convergence holds as node count grows (fixed per-node
    batch: more nodes = more parallel work per superstep)."""
    steps = 20 if quick else 50
    out = {}
    for n in ([4, 16] if quick else [4, 8, 16, 32]):
        r = run_steps(BenchSetup(n_nodes=n), "swarm", steps)
        out[n] = r
        emit(f"t9_node_scaling/n{n}", r["us_per_step"],
             f"final_loss={np.mean(r['loss'][-5:]):.4f};"
             f"gamma={np.mean(r['gamma'][-5:]):.4g}")
    save("t9_node_scaling", {str(k): {"loss": v["loss"]}
                             for k, v in out.items()})
    return out


def t8_transport(quick=False):
    """Flat-buffer vs per-leaf legacy gossip on the bench transformer, for
    the gather transport AND the production ppermute_pool transport (lax.
    switch over K static matchings), exact + 8-bit quantized.

    The flat path issues one collective / one kernel sweep per payload
    tensor; the legacy path issues one PER LEAF — and the pool multiplies
    that by K branches, so legacy compile time scales K×L while flat stays
    K×(1 or 2). Reported per variant: compile_s, steady-state us_per_call,
    and traj_total_s = compile + steps×steady for the t1-length trajectory
    (the honest single-host cost of training with that transport; on real
    meshes the collective-count collapse also cuts per-step latency, which
    a one-device simulation cannot show — DESIGN.md §Perf)."""
    import time

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from benchmarks.common import BenchSetup, bench_stacked_params
    from repro.core import bucket as B
    from repro.core.graph import make_graph, sample_matching
    from repro.core.swarm import (gossip_exact, gossip_ppermute_pool,
                                  gossip_quantized, make_matching_pool)
    from repro.quant.schemes import ModularQuantConfig

    reps = 5 if quick else 20
    traj_steps = 25 if quick else 80   # matches run_steps() in t1/t3
    setup = BenchSetup()
    n = setup.n_nodes
    params = bench_stacked_params(setup, spread=0.01)
    prev = jax.tree.map(lambda x: x + 0.005, params)
    qcfg = ModularQuantConfig(safety=16.0)
    rng_np = np.random.default_rng(0)
    graph = make_graph("complete", n)
    perm = jnp.asarray(sample_matching(graph, rng_np))
    matched = perm != jnp.arange(n)
    pool = make_matching_pool(graph, K=2 if quick else 4, seed=0)
    pool_idx = jnp.asarray(1)
    mesh = jax.make_mesh((1,), ("node",))
    specs = jax.tree.map(lambda x: P(*((None,) * x.ndim)), params)
    key = jax.random.PRNGKey(0)
    n_leaves = len(jax.tree.leaves(params))
    n_params = sum(x.size for x in jax.tree.leaves(params)) // n

    def pack_gossip_unpack(tree, gossip, *packed_extra):
        lay = B.build_layout(tree, block=qcfg.block)
        return B.unpack(lay, gossip(B.pack(lay, tree), lay, *packed_extra))

    variants = {
        "gather_exact_legacy": (lambda t: gossip_exact(t, perm, matched),
                                (params,)),
        "gather_exact_flat": (lambda t: pack_gossip_unpack(
            t, lambda b, lay: B.gossip_flat_exact(b, perm, matched)),
            (params,)),
        "gather_q8_legacy": (lambda t, pv, k: gossip_quantized(
            qcfg, t, pv, perm, matched, k), (params, prev, key)),
        "gather_q8_flat": (lambda t, pv, k: pack_gossip_unpack(
            t, lambda b, lay: B.gossip_flat_quantized(
                qcfg, b, B.pack(lay, pv), perm, matched, k)),
            (params, prev, key)),
        "pool_exact_legacy": (lambda t, i: gossip_ppermute_pool(
            t, specs, mesh, (), pool, i), (params, pool_idx)),
        "pool_exact_flat": (lambda t, i: pack_gossip_unpack(
            t, lambda b, lay: B.gossip_flat_ppermute_pool(
                b, mesh, (), pool, i)), (params, pool_idx)),
        "pool_q8_legacy": (lambda t, pv, i, k: gossip_ppermute_pool(
            t, specs, mesh, (), pool, i, quant=qcfg, prev=pv, rng=k),
            (params, prev, pool_idx, key)),
        "pool_q8_flat": (lambda t, pv, i, k: pack_gossip_unpack(
            t, lambda b, lay: B.gossip_flat_ppermute_pool(
                b, mesh, (), pool, i, quant=qcfg, prev_buf=B.pack(lay, pv),
                rng=k)), (params, prev, pool_idx, key)),
    }

    out = {"n_leaves": n_leaves, "n_params_per_node": n_params,
           "pool_K": len(pool), "traj_steps": traj_steps}
    with mesh:
        for name, (fn, args) in variants.items():
            jf = jax.jit(fn)
            t0 = time.time()
            jax.block_until_ready(jf(*args))
            compile_s = time.time() - t0
            t0 = time.time()
            for _ in range(reps):
                jax.block_until_ready(jf(*args))
            us = (time.time() - t0) / reps * 1e6
            total = compile_s + traj_steps * us / 1e6
            out[name] = {"us_per_call": us, "compile_s": compile_s,
                         "traj_total_s": total}
            emit(f"t8_transport/{name}", us,
                 f"compile_s={compile_s:.2f};traj_total_s={total:.2f}")
    for mode in ["gather_exact", "gather_q8", "pool_exact", "pool_q8"]:
        sp = out[f"{mode}_legacy"]["traj_total_s"] / \
            out[f"{mode}_flat"]["traj_total_s"]
        cp = out[f"{mode}_legacy"]["compile_s"] / \
            out[f"{mode}_flat"]["compile_s"]
        out[f"{mode}_traj_speedup"] = sp
        out[f"{mode}_compile_speedup"] = cp
        emit(f"t8_transport/{mode}_speedup", 0.0,
             f"traj_flat_vs_legacy={sp:.2f}x;compile={cp:.2f}x")
    save("t8_transport", out)
    return out


def t9_async(quick=False):
    """DESIGN.md §Pipeline: blocking vs plain non-blocking vs the
    double-buffered overlapped superstep on the production quantized
    ppermute_pool transport — full supersteps (local loop + gossip), same
    model, same batches and matchings. The variants are advanced ROUND-ROBIN
    and compared PAIRED per round (median of per-round time differences),
    so drifting background load hits all of them equally instead of
    whichever happened to run in a noisy window. Also reports compile time
    (the pool's lax.switch holds only payload permutes in overlap mode, vs
    K×(encode+permute+decode) blocking). On a single-host CPU there is no
    wire latency to hide, so the steady-state win is the removed second
    pack + per-leaf comm-copy refresh; on a real mesh the collective itself
    overlaps the local-step loop (the point of the pipeline)."""
    import time

    import jax
    import jax.numpy as jnp

    from benchmarks.common import build
    from repro.core.swarm import sample_h_counts
    from repro.data import make_node_batches

    rounds = 12 if quick else 40
    setup = BenchSetup()
    variants = {
        "blocking": dict(),
        "nonblocking": dict(nonblocking=True),
        "overlap": dict(nonblocking=True, overlap=True),
    }
    runs, out = {}, {}
    for name, kw in variants.items():
        cfg, graph, scfg, step, state, ds = build(
            setup, "swarm", quantize=True, gossip_impl="ppermute_pool",
            pool_size=4, **kw)
        runs[name] = dict(scfg=scfg, step=step, state=state, ds=ds,
                          rng_np=np.random.default_rng(setup.seed),
                          key=jax.random.PRNGKey(setup.seed + 1),
                          times=[], losses=[])

    def one_step(r, t):
        scfg = r["scfg"]
        nb = make_node_batches(r["ds"], t, setup.batch * scfg.H)
        batch = {k: jnp.asarray(v.reshape(setup.n_nodes, scfg.H, setup.batch,
                                          setup.seq))
                 for k, v in nb.items()}
        idx = int(r["rng_np"].integers(scfg.pool_size))
        perm = jnp.full((setup.n_nodes,), idx, jnp.int32)
        h = jnp.asarray(sample_h_counts(scfg, r["rng_np"]))
        r["key"], sub = jax.random.split(r["key"])
        t0 = time.time()
        r["state"], m = r["step"](r["state"], batch, perm, h, sub)
        m = jax.device_get(m)
        dt = time.time() - t0
        r["times"].append(dt)
        r["losses"].append(float(m["loss"]))
        return dt

    for name in runs:                                  # compile round
        runs[name]["compile_s"] = one_step(runs[name], 0)
    for t in range(1, rounds + 1):                     # interleaved rounds
        for name in runs:
            one_step(runs[name], t)

    for name, r in runs.items():
        # drop round 1 (allocator warm-up), keep the paired remainder
        steady = np.asarray(r["times"][2:]) * 1e6
        out[name] = {"us_per_step_med": float(np.median(steady)),
                     "us_per_step_min": float(np.min(steady)),
                     "compile_s": r["compile_s"],
                     "final_loss": float(np.mean(r["losses"][-5:]))}
        emit(f"t9_async/{name}", out[name]["us_per_step_med"],
             f"min_us={out[name]['us_per_step_min']:.0f};"
             f"compile_s={r['compile_s']:.2f};"
             f"final_loss={out[name]['final_loss']:.4f}")
    paired = np.asarray(runs["blocking"]["times"][2:]) - \
        np.asarray(runs["overlap"]["times"][2:])
    out["paired_median_blocking_minus_overlap_us"] = \
        float(np.median(paired) * 1e6)
    ratio = out["blocking"]["us_per_step_med"] / \
        out["overlap"]["us_per_step_med"]
    cratio = out["blocking"]["compile_s"] / out["overlap"]["compile_s"]
    out["overlap_speedup_vs_blocking"] = ratio
    out["overlap_compile_speedup_vs_blocking"] = cratio
    out["overlap_leq_blocking"] = bool(np.median(paired) >= 0)
    emit("t9_async/overlap_vs_blocking", 0.0,
         f"step_speedup={ratio:.2f}x;compile_speedup={cratio:.2f}x;"
         f"paired_median_saving_us="
         f"{out['paired_median_blocking_minus_overlap_us']:.0f};"
         f"overlap_leq_blocking={out['overlap_leq_blocking']}")
    save("t9_async", out)
    return out


def t10_sched(quick=False):
    """DESIGN.md §Sched: the discrete-event scheduler end to end — for
    each rate profile, generate a Poisson trace, compile it to masked
    supersteps, run the bridged engine (training still works under
    heterogeneous participation), and report the wall-clock cost model's
    predicted (closed-form) vs simulated (event-replay) end-to-end time
    for blocking / non-blocking / overlap. The uniform (synchronous)
    profile is the anchor: its bridged trajectory must equal the plain
    unscheduled engine BIT-EXACTLY (asserted here)."""
    import time

    import jax
    import jax.numpy as jnp

    from benchmarks.common import BenchSetup, build, run_steps
    from repro.core.graph import make_graph
    from repro.data import make_node_batches
    from repro.sched import (RateProfile, StragglerConfig, bin_trace,
                             cost_params_from_model, engine_inputs,
                             generate_trace, predict_all_modes,
                             synchronous_trace, trace_stats)

    steps = 8 if quick else 25
    setup = BenchSetup()
    n = setup.n_nodes
    graph = make_graph("complete", n)
    h_max_async = 8

    def run_binned(sched, h_mode, h_max):
        # h_max reaches SwarmConfig through build(): the engine's loop
        # bound, the batch depth, and the trace clip all share one value
        cfg, g, scfg, step, state, ds = build(setup, "swarm", h_mode=h_mode,
                                              h_max=h_max)
        assert scfg.h_max == h_max or h_mode == "fixed"
        key = jax.random.PRNGKey(setup.seed + 1)
        losses, gammas, times = [], [], []
        for s in range(sched.n_supersteps):
            nb = make_node_batches(ds, s, setup.batch * h_max)
            batch = {k: jnp.asarray(v.reshape(n, h_max, setup.batch,
                                              setup.seq))
                     for k, v in nb.items()}
            perm, h, mask = engine_inputs(sched, s, scfg.gossip_impl)
            key, sub = jax.random.split(key)
            t0 = time.time()
            state, m = step(state, batch, jnp.asarray(perm),
                            jnp.asarray(h), sub, jnp.asarray(mask))
            m = jax.device_get(m)
            times.append(time.time() - t0)
            losses.append(float(m["loss"]))
            gammas.append(float(m.get("gamma", 0.0)))
        return cfg, losses, gammas, times

    profiles = {
        "uniform": dict(kind="sync"),
        "lognormal": dict(kind="lognormal", sigma=0.8),
        "straggler": dict(kind="lognormal", sigma=0.5,
                          straggler=StragglerConfig(fraction=0.25,
                                                    slowdown=8.0)),
    }
    if not quick:
        profiles["uniform_async"] = dict(kind="uniform")

    out = {}
    cost = cost_q8 = None
    uniform_losses = None
    for name, spec in profiles.items():
        if spec["kind"] == "sync":
            trace = synchronous_trace(graph, steps, H=setup.H,
                                      rng=np.random.default_rng(setup.seed))
            h_mode, h_max = "fixed", setup.H
        else:
            trace = generate_trace(
                graph, RateProfile(spec["kind"],
                                   sigma=spec.get("sigma", 0.5)),
                steps * (n // 2), H=setup.H, h_max=h_max_async,
                seed=setup.seed,
                straggler=spec.get("straggler", StragglerConfig()))
            h_mode, h_max = "trace", h_max_async
        sched = bin_trace(trace)
        cfg, losses, gammas, times = run_binned(sched, h_mode, h_max)
        if name == "uniform":
            uniform_losses = (losses, gammas)
        if cost is None:
            cost = cost_params_from_model(cfg, seq_len=setup.seq,
                                          local_batch=setup.batch)
            cost_q8 = cost_params_from_model(cfg, seq_len=setup.seq,
                                             local_batch=setup.batch,
                                             quantize=True)
        pred = predict_all_modes(trace, cost)
        pred_q8 = predict_all_modes(trace, cost_q8)
        stats = {k: v for k, v in trace_stats(trace).items()
                 if not isinstance(v, list)}
        out[name] = {
            "n_events": trace.n_events,
            "n_supersteps": sched.n_supersteps,
            "density": sched.density(),
            "trace_stats": stats,
            "final_loss": float(np.mean(losses[-5:])),
            "host_us_per_superstep": float(np.mean(times[2:]) * 1e6)
            if len(times) > 2 else float("nan"),
            "walltime_fp32": pred,
            "walltime_q8": pred_q8,
        }
        emit(f"t10_sched/{name}", out[name]["host_us_per_superstep"],
             f"bins={sched.n_supersteps};density={sched.density():.2f};"
             f"effH={stats['effective_H']:.2f};"
             f"final_loss={out[name]['final_loss']:.4f};"
             f"pred_blocking_s={pred['blocking']['predicted_s']:.4g};"
             f"sim_blocking_s={pred['blocking']['simulated_s']:.4g};"
             f"nb_speedup={pred['speedup_nonblocking_vs_blocking']:.2f}x")

    # the synchronous uniform profile must reproduce the PLAIN engine
    # trajectory bit-exactly (same matchings, same batches, full masks):
    # gamma is a pure function of the param trajectory, so equality of the
    # gamma series IS trajectory bit-exactness
    plain = run_steps(setup, "swarm", steps)
    exact = plain["gamma"] == uniform_losses[1] and \
        plain["loss"] == uniform_losses[0]
    out["uniform"]["bit_exact_vs_plain"] = bool(exact)
    emit("t10_sched/uniform_bit_exact", 0.0, f"ok={exact}")
    assert exact, "uniform sync profile must be bit-exact with the plain " \
        "superstep engine"
    save("t10_sched", out)
    return out


def t11_baselines(quick=False):
    """DESIGN.md §Baselines: every algorithm on the unified exchange layer
    under ONE lognormal rate profile — SwarmSGD vs AD-PSGD vs SGP vs
    LocalSGD, fp32 + q8 where the capability matrix allows, each trained
    end-to-end through the scheduler bridge (masked supersteps) with the
    wall-clock cost model's predicted-vs-simulated end-to-end time:
    pairwise algorithms (swarm/adpsgd/sgp) via per-event replay, the
    bulk-synchronous LocalSGD via the per-bin global-rendezvous model.
    Emits results/bench/t11_baselines.json (CI artifact)."""
    import time

    import jax
    import jax.numpy as jnp

    from benchmarks.common import build
    from repro.algorithms import CAPABILITIES
    from repro.core.graph import make_graph
    from repro.data import make_node_batches
    from repro.sched import (RateProfile, bin_trace, bsp_payload_factor,
                             cost_params_from_model, engine_inputs,
                             generate_trace, predict_all_modes,
                             predict_bsp_walltime)

    steps = 8 if quick else 25
    setup = BenchSetup()
    n = setup.n_nodes
    graph = make_graph("complete", n)
    h_max_async = 8

    algos = ["swarm", "adpsgd", "sgp", "localsgd"]
    variants = [(a, q) for a in algos
                for q in ([False, True] if CAPABILITIES[a].quantized
                          else [False])]
    out = {"profile": "lognormal", "sigma": 0.8, "steps": steps,
           "n_nodes": n}
    cost_cache = {}
    for algo, quantize in variants:
        caps = CAPABILITIES[algo]
        H_eff = setup.H if caps.local_H else 1
        h_max = h_max_async if caps.local_H else 1
        trace = generate_trace(graph, RateProfile("lognormal", sigma=0.8),
                               steps * (n // 2), H=H_eff, h_max=h_max,
                               h_mode="rate", seed=setup.seed)
        sched = bin_trace(trace)
        cfg, g, scfg, step, state, ds = build(
            setup, algo, quantize=quantize,
            h_mode="trace" if caps.local_H else "fixed", h_max=h_max,
            rate_profile="lognormal")
        slots = scfg.h_loop_bound
        key = jax.random.PRNGKey(setup.seed + 1)
        losses, times = [], []
        for s in range(sched.n_supersteps):
            nb = make_node_batches(ds, s, setup.batch * slots)
            batch = {k: jnp.asarray(v.reshape(n, slots, setup.batch,
                                              setup.seq))
                     for k, v in nb.items()}
            perm, h, mask = engine_inputs(sched, s, scfg.gossip_impl)
            key, sub = jax.random.split(key)
            t0 = time.time()
            state, m = step(state, batch, jnp.asarray(perm),
                            jnp.asarray(h), sub, jnp.asarray(mask))
            m = jax.device_get(m)
            times.append(time.time() - t0)
            losses.append(float(m["loss"]))
        ck = quantize
        if ck not in cost_cache:
            cost_cache[ck] = cost_params_from_model(
                cfg, seq_len=setup.seq, local_batch=setup.batch,
                quantize=quantize)
        cp = cost_cache[ck]
        if caps.pricing == "pairwise":
            pred = predict_all_modes(trace, cp)
            wall = {"simulated_s": pred["blocking"]["simulated_s"],
                    "predicted_s": pred["blocking"]["predicted_s"],
                    "all_modes": pred}
        else:
            rep = predict_bsp_walltime(
                trace, sched, cp,
                payload_factor=bsp_payload_factor(algo, graph))
            wall = {"simulated_s": rep["total_s"],
                    "predicted_s": rep["analytic_s"],
                    "wait_frac": rep["wait_frac"]}
        name = f"{algo}_{'q8' if quantize else 'fp32'}"
        out[name] = {
            "pricing": caps.pricing,
            "n_supersteps": sched.n_supersteps,
            "density": sched.density(),
            "final_loss": float(np.mean(losses[-5:])),
            "host_us_per_superstep": float(np.mean(times[2:]) * 1e6)
            if len(times) > 2 else float("nan"),
            "walltime": wall,
        }
        emit(f"t11_baselines/{name}",
             out[name]["host_us_per_superstep"],
             f"final_loss={out[name]['final_loss']:.4f};"
             f"bins={sched.n_supersteps};"
             f"sim_s={wall['simulated_s']:.4g};"
             f"pred_s={wall['predicted_s']:.4g};"
             f"pred_over_sim="
             f"{wall['predicted_s'] / max(wall['simulated_s'], 1e-30):.2f}")
    # headline: predicted wall-clock of each baseline relative to swarm
    # (same profile, same cost model — the paper's Fig 7 shape)
    ref = out["swarm_fp32"]["walltime"]["simulated_s"]
    for algo in algos[1:]:
        k = f"{algo}_fp32"
        out[f"{algo}_vs_swarm_walltime"] = \
            out[k]["walltime"]["simulated_s"] / max(ref, 1e-30)
        emit(f"t11_baselines/{algo}_vs_swarm", 0.0,
             f"walltime_ratio={out[f'{algo}_vs_swarm_walltime']:.2f}x")
    save("t11_baselines", out)
    return out


def t12_codecs(quick=False):
    """DESIGN.md §Codec: the codec sweep — swarm and AD-PSGD × {fp32, q8,
    q4, topk:0.25} trained end-to-end through the scheduler bridge on ONE
    lognormal rate profile, with (a) the MEASURED packed wire bytes of
    each codec's real encoded arrays asserted against the declared
    WireLayout, and (b) the wall-clock cost model's predicted-vs-simulated
    end-to-end time priced from those codec bytes — the honest per-codec
    communication story (q4 ≈ half the q8 wire; top-k below that at the
    cost of the EF residual state). Emits results/bench/t12_codecs.json
    (CI artifact)."""
    import time

    import jax
    import jax.numpy as jnp

    from benchmarks.common import build, measured_payload
    from repro.algorithms import CAPABILITIES
    from repro.core.graph import make_graph
    from repro.data import make_node_batches
    from repro.sched import (RateProfile, bin_trace, cost_params_from_model,
                             engine_inputs, generate_trace,
                             predict_all_modes)

    steps = 8 if quick else 25
    setup = BenchSetup()
    n = setup.n_nodes
    graph = make_graph("complete", n)
    h_max_async = 8

    codecs = [None, "q8", "q4", "topk:0.25"]   # None = fp32 (no --quantize)
    mp = measured_payload(codecs=("q8", "q4", "topk:0.25"))
    out = {"profile": "lognormal", "sigma": 0.8, "steps": steps,
           "n_nodes": n, "measured_payload": mp}
    for key in [k[:-len("_payload_bytes")] for k in mp
                if k.endswith("_payload_bytes")]:
        assert mp[f"{key}_payload_bytes"] == mp[f"{key}_formula_bytes"], key
    assert mp["q4_payload_bytes"] < 0.55 * mp["q8_payload_bytes"]

    for algo in ["swarm", "adpsgd"]:
        caps = CAPABILITIES[algo]
        H_eff = setup.H if caps.local_H else 1
        h_max = h_max_async if caps.local_H else 1
        trace = generate_trace(graph, RateProfile("lognormal", sigma=0.8),
                               steps * (n // 2), H=H_eff, h_max=h_max,
                               h_mode="rate", seed=setup.seed)
        sched = bin_trace(trace)
        for codec in codecs:
            quantize = codec is not None
            cfg, g, scfg, step, state, ds = build(
                setup, algo, quantize=quantize, codec=codec,
                h_mode="trace" if caps.local_H else "fixed", h_max=h_max,
                rate_profile="lognormal")
            slots = scfg.h_loop_bound
            key = jax.random.PRNGKey(setup.seed + 1)
            losses, times = [], []
            for s in range(sched.n_supersteps):
                nb = make_node_batches(ds, s, setup.batch * slots)
                batch = {k: jnp.asarray(v.reshape(n, slots, setup.batch,
                                                  setup.seq))
                         for k, v in nb.items()}
                perm, h, mask = engine_inputs(sched, s, scfg.gossip_impl)
                key, sub = jax.random.split(key)
                t0 = time.time()
                state, m = step(state, batch, jnp.asarray(perm),
                                jnp.asarray(h), sub, jnp.asarray(mask))
                m = jax.device_get(m)
                times.append(time.time() - t0)
                losses.append(float(m["loss"]))
            cp = cost_params_from_model(cfg, seq_len=setup.seq,
                                        local_batch=setup.batch,
                                        quantize=quantize, codec=codec)
            pred = predict_all_modes(trace, cp)
            name = f"{algo}_{(codec or 'fp32').replace(':', '_')}"
            out[name] = {
                "codec": cp.meta["codec"],
                "payload_bytes": cp.payload_bytes,
                "n_supersteps": sched.n_supersteps,
                "final_loss": float(np.mean(losses[-5:])),
                "host_us_per_superstep": float(np.mean(times[2:]) * 1e6)
                if len(times) > 2 else float("nan"),
                "walltime": {
                    "simulated_s": pred["blocking"]["simulated_s"],
                    "predicted_s": pred["blocking"]["predicted_s"],
                    "all_modes": pred},
            }
            emit(f"t12_codecs/{name}", out[name]["host_us_per_superstep"],
                 f"final_loss={out[name]['final_loss']:.4f};"
                 f"payload={cp.payload_bytes}B;"
                 f"sim_s={pred['blocking']['simulated_s']:.4g};"
                 f"pred_s={pred['blocking']['predicted_s']:.4g}")
        # headline per algo: wire ratio + modeled wall-clock ratio vs fp32
        fp = out[f"{algo}_fp32"]
        for codec in codecs[1:]:
            k = f"{algo}_{codec.replace(':', '_')}"
            out[f"{k}_vs_fp32"] = {
                "wire_ratio": fp["payload_bytes"] / out[k]["payload_bytes"],
                "walltime_ratio": fp["walltime"]["simulated_s"] /
                max(out[k]["walltime"]["simulated_s"], 1e-30),
            }
            emit(f"t12_codecs/{k}_vs_fp32", 0.0,
                 f"wire={out[f'{k}_vs_fp32']['wire_ratio']:.2f}x;"
                 f"walltime={out[f'{k}_vs_fp32']['walltime_ratio']:.2f}x")
    save("t12_codecs", out)
    return out


def t13_fused(quick=False):
    """DESIGN.md §Fusion: scan-driven superstep vs the per-step driver —
    host dispatch cost per superstep, fp32 and q8, at the t12 bench
    config. Both drivers run the SAME jitted superstep on the SAME
    presampled schedule rows (pre-split per-step/per-chunk device arrays,
    as the production driver ships them) and pre-staged device batches.
    The per-step driver issues CHUNK dispatches plus CHUNK eager key
    splits; the scan driver folds them into ONE lax.scan dispatch.
    Dispatch on CPU is asynchronous, so the timed region is the
    UN-BLOCKED dispatch loop — pure host-side cost, the thing the scan
    amortizes — with block_until_ready outside it (the per-step loop is
    windowed at 8 dispatches so the CPU client's in-flight backpressure
    never turns dispatch synchronous inside a timed region); both sides
    are timed without donation because on the CPU backend an execution
    whose input buffers are actually CONSUMED by donation runs
    synchronously (the
    production donated path is timed separately as wall clock per
    superstep — same compute, host waits inside the dispatch instead of
    at the metrics fetch; see DESIGN.md §Fusion). Variants advance
    ROUND-ROBIN and are compared PAIRED per round (t9 style) so drifting
    background load hits all of them equally. Acceptance: scan
    host_us_per_superstep >= 5x below per-step for both codecs. Also
    reports compile time and donated-vs-perstep wall parity. Emits
    results/bench/t13_fused.json (CI artifact)."""
    import time

    import jax
    import jax.numpy as jnp

    from benchmarks.common import build
    from repro.core import make_superstep_scan
    from repro.core.swarm import sample_h_counts
    from repro.data import make_node_batches
    from repro.launch.train import sample_gossip_perm

    rounds = 2 if quick else 8
    chunk = 32
    setup = BenchSetup()
    out = {}
    for cname, kw in [("fp32", dict()), ("q8", dict(quantize=True))]:
        cfg, graph, scfg, step, state, ds = build(setup, "swarm", **kw)
        scan_fn = make_superstep_scan(step, donate=False)
        don_fn = make_superstep_scan(step, donate=True)
        h_max = scfg.h_loop_bound
        # presample the WHOLE schedule host-side once, ship pre-split —
        # exactly the production driver's input path (indexing a stacked
        # device array with fresh python ints would recompile per step)
        rng_np = np.random.default_rng(setup.seed)
        total = (rounds + 1) * chunk
        perm_np = np.stack([sample_gossip_perm(scfg, graph, rng_np,
                                               setup.seed)
                            for _ in range(total)])
        h_np = np.stack([np.asarray(sample_h_counts(scfg, rng_np))
                         for _ in range(total)])
        perm_rows = [jnp.asarray(p) for p in perm_np]
        h_rows = [jnp.asarray(h) for h in h_np]
        perm_cks = [jnp.asarray(perm_np[t:t + chunk])
                    for t in range(0, total, chunk)]
        h_cks = [jnp.asarray(h_np[t:t + chunk])
                 for t in range(0, total, chunk)]
        st_ps = jax.tree.map(jnp.copy, state)       # per-step driver
        st_sc = jax.tree.map(jnp.copy, state)       # scan, host-cost timed
        st_dn = jax.tree.map(jnp.copy, state)       # scan, donated (prod)
        key_ps = jax.random.PRNGKey(setup.seed + 1)
        key_sc = jax.random.PRNGKey(setup.seed + 1)
        key_dn = jax.random.PRNGKey(setup.seed + 1)
        ps_host, sc_host, ps_wall, dn_wall = [], [], [], []
        compile_ps = compile_sc = 0.0
        shp = (setup.n_nodes, h_max, setup.batch, setup.seq)
        for r in range(rounds + 1):
            t0 = r * chunk
            nbs = [make_node_batches(ds, t0 + i, setup.batch * h_max)
                   for i in range(chunk)]
            steps_b = [{k: jnp.asarray(v.reshape(shp)) for k, v in nb.items()}
                       for nb in nbs]
            stacked_b = {k: jnp.stack([b[k] for b in steps_b])
                         for k in steps_b[0]}
            jax.block_until_ready((steps_b, stacked_b, st_ps, st_sc, st_dn))
            # per-step: CHUNK dispatches, timed un-blocked in windows of 8
            # — past ~8 in-flight executions the CPU client backpressures
            # and dispatch degenerates to synchronous, which would report
            # device compute as host cost; the windows keep the per-step
            # number the actual host-loop cost (split + flatten + call)
            t1 = time.perf_counter()
            dt_ps = 0.0
            for w in range(0, chunk, 8):
                tw = time.perf_counter()
                for i in range(w, min(w + 8, chunk)):
                    key_ps, sub = jax.random.split(key_ps)
                    st_ps, _ = step(st_ps, steps_b[i], perm_rows[t0 + i],
                                    h_rows[t0 + i], sub)
                dt_ps += time.perf_counter() - tw
                jax.block_until_ready(st_ps)
            wall_ps = time.perf_counter() - t1
            t1 = time.perf_counter()            # scan: ONE dispatch
            res = scan_fn(st_sc, key_sc, stacked_b, perm_cks[r], h_cks[r])
            dt_sc = time.perf_counter() - t1
            jax.block_until_ready(res)
            st_sc, key_sc, _ = res
            t1 = time.perf_counter()            # donated scan: wall clock
            st_dn, key_dn, ms = don_fn(st_dn, key_dn, stacked_b,
                                       perm_cks[r], h_cks[r])
            jax.block_until_ready((st_dn, ms))
            wall_dn = time.perf_counter() - t1
            if r == 0:                          # compile round
                compile_ps, compile_sc = dt_ps, dt_sc
            else:
                ps_host.append(dt_ps)
                sc_host.append(dt_sc)
                ps_wall.append(wall_ps)
                dn_wall.append(wall_dn)
        ps_us = np.asarray(ps_host) * 1e6 / chunk
        sc_us = np.asarray(sc_host) * 1e6 / chunk
        paired = np.median(ps_us - sc_us)
        row = {
            "perstep": {"host_us_per_superstep": float(np.median(ps_us)),
                        "host_us_min": float(np.min(ps_us)),
                        "wall_us_per_superstep": float(
                            np.median(ps_wall) * 1e6 / chunk),
                        "compile_s": compile_ps},
            "scan": {"host_us_per_superstep": float(np.median(sc_us)),
                     "host_us_min": float(np.min(sc_us)),
                     "compile_s": compile_sc},
            "scan_donated": {"wall_us_per_superstep": float(
                np.median(dn_wall) * 1e6 / chunk)},
            "chunk": chunk,
            "paired_median_saving_us": float(paired),
            "scan_speedup": float(np.median(ps_us) / np.median(sc_us)),
        }
        row["speedup_ok"] = bool(row["scan_speedup"] >= 5.0)
        row["donated_wall_ratio_vs_perstep"] = \
            row["scan_donated"]["wall_us_per_superstep"] / \
            row["perstep"]["wall_us_per_superstep"]
        out[cname] = row
        emit(f"t13_fused/{cname}_perstep",
             row["perstep"]["host_us_per_superstep"],
             f"compile_s={compile_ps:.2f};"
             f"wall_us={row['perstep']['wall_us_per_superstep']:.0f}")
        emit(f"t13_fused/{cname}_scan",
             row["scan"]["host_us_per_superstep"],
             f"compile_s={compile_sc:.2f};"
             f"donated_wall_us="
             f"{row['scan_donated']['wall_us_per_superstep']:.0f}")
        emit(f"t13_fused/{cname}_speedup", 0.0,
             f"scan_speedup={row['scan_speedup']:.1f}x;"
             f"paired_saving_us={paired:.0f};ok={row['speedup_ok']};"
             f"donated_wall_ratio="
             f"{row['donated_wall_ratio_vs_perstep']:.2f}")
    save("t13_fused", out)
    return out


def t14_churn(quick=False):
    """DESIGN.md §Churn: elastic membership end to end — a day/night
    availability model (late joiners + permanent leavers) composed with a
    lognormal rate profile, the churn trace compiled to bins, the bridged
    engine trained through the driver's churn loop (retire before the
    bin, packed join bootstrap on join bins, masked gossip superstep
    otherwise), and the kind-aware wall-clock cost model — leaves priced
    zero, a join priced as one bootstrap payload delivered to the joiner
    — reported as predicted vs simulated end-to-end time against the same
    profile WITHOUT churn. Emits results/bench/t14_churn.json (CI
    artifact)."""
    import time

    import jax
    import jax.numpy as jnp

    from benchmarks.common import build
    from repro.core import make_graph, make_join_step, retire_nodes
    from repro.data import make_node_batches
    from repro.sched import (EVENT_JOIN, PoissonClocks, RateProfile,
                             bin_trace, cost_params_from_model,
                             generate_trace, parse_avail, predict_all_modes,
                             predict_walltime, trace_stats)

    setup = BenchSetup()
    n = setup.n_nodes
    graph = make_graph("complete", n)
    h_max = 8
    n_events = 40 if quick else 100
    spec = os.environ.get(
        "REPRO_AVAIL_PROFILE",
        "day_night:period=8,duty=0.6,join=0.3:1:5,leave=0.3:6:18,seed=3")
    prof = RateProfile("lognormal", sigma=0.8)

    av = parse_avail(spec, n, seed=0)
    clocks = PoissonClocks(graph, prof.make_rates(n, setup.seed),
                           setup.seed, avail=av)
    trace = generate_trace(graph, prof, n_events, H=setup.H, h_max=h_max,
                           h_mode="rate", seed=setup.seed, clocks=clocks)
    plain = generate_trace(graph, prof, n_events, H=setup.H, h_max=h_max,
                           h_mode="rate", seed=setup.seed)
    sched = bin_trace(trace)
    stats = {k: v for k, v in trace_stats(trace).items()
             if not isinstance(v, list)}

    cfg, g, scfg, step, state, ds = build(setup, "swarm", quantize=True,
                                          h_mode="trace", h_max=h_max,
                                          rate_profile="lognormal")
    join_fn = jax.jit(make_join_step(scfg))
    key = jax.random.PRNGKey(setup.seed + 1)
    losses, times, join_times = [], [], []
    for s in range(sched.n_supersteps):
        if sched.retire[s].any():
            state = retire_nodes(state, jnp.asarray(sched.retire[s]))
        if sched.kinds[s] == EVENT_JOIN:
            t0 = time.time()
            state = join_fn(state, jnp.asarray(sched.perms[s]),
                            jnp.asarray(sched.mask[s]))
            jax.block_until_ready(state.params)
            join_times.append(time.time() - t0)
            continue
        nb = make_node_batches(ds, s, setup.batch * h_max)
        batch = {k: jnp.asarray(v.reshape(n, h_max, setup.batch, setup.seq))
                 for k, v in nb.items()}
        key, sub = jax.random.split(key)
        t0 = time.time()
        state, m = step(state, batch, jnp.asarray(sched.perms[s]),
                        jnp.asarray(sched.h[s]), sub,
                        jnp.asarray(sched.mask[s]))
        m = jax.device_get(m)
        times.append(time.time() - t0)
        losses.append(float(m["loss"]))
    if sched.retire[sched.n_supersteps].any():
        state = retire_nodes(state,
                             jnp.asarray(sched.retire[sched.n_supersteps]))
    assert trace.meta["n_joins"] > 0 and trace.meta["n_leaves"] > 0, \
        "churn spec degenerated to fixed membership — benchmark is a no-op"

    cp = cost_params_from_model(cfg, seq_len=setup.seq,
                                local_batch=setup.batch, quantize=True)
    pred = predict_all_modes(trace, cp)
    pred_plain = predict_all_modes(plain, cp)
    # the kind-aware pricing detail (leaves free, joins one payload) rides
    # on the event replay, which predict_all_modes summarizes away
    rep = predict_walltime(trace, cp, mode="blocking")
    out = {
        "avail_spec": spec,
        "n_events": trace.n_events,
        "n_supersteps": sched.n_supersteps,
        "n_joins": trace.meta["n_joins"],
        "n_leaves": trace.meta["n_leaves"],
        "alive_final": int((sched.alive[-1] &
                            ~sched.retire[sched.n_supersteps]).sum()),
        "trace_stats": stats,
        "final_loss": float(np.mean(losses[-5:])),
        "host_us_per_superstep": float(np.mean(times[2:]) * 1e6)
        if len(times) > 2 else float("nan"),
        "join_bootstrap_us": float(np.mean(join_times) * 1e6)
        if join_times else float("nan"),
        "walltime_churn": pred,
        "walltime_no_churn": pred_plain,
        "join_comm_s": rep["join_comm_s"],
    }
    assert rep["n_joins"] == trace.meta["n_joins"]
    b = pred["blocking"]
    emit("t14_churn/day_night", out["host_us_per_superstep"],
         f"bins={sched.n_supersteps};joins={out['n_joins']};"
         f"leaves={out['n_leaves']};alive_final={out['alive_final']};"
         f"final_loss={out['final_loss']:.4f};"
         f"pred_s={b['predicted_s']:.4g};sim_s={b['simulated_s']:.4g};"
         f"join_comm_s={rep['join_comm_s']:.4g}")
    ratio = b["simulated_s"] / \
        max(pred_plain["blocking"]["simulated_s"], 1e-30)
    out["churn_vs_no_churn_walltime"] = ratio
    emit("t14_churn/vs_no_churn", 0.0,
         f"walltime_ratio={ratio:.2f}x;"
         f"join_bootstrap_us={out['join_bootstrap_us']:.0f}")
    save("t14_churn", out)
    return out


def t15_serve(quick=False):
    """DESIGN.md §Serving: the continuous-batching engine under a
    synthetic open-loop Poisson arrival process on CPU, with a fresh swarm
    mean model landing MID-RUN through the hot-swap path. Reports
    tokens/s, p50/p99 per-token latency, queue depth, and
    time-to-fresh-model; asserts the serving contract — at least one model
    refresh adopted, zero in-flight sequences dropped, zero decode-step
    recompiles after warmup (jit-cache-miss counter). Emits
    results/bench/t15_serve.json (CI artifact)."""
    import time

    import jax

    from repro.configs import get_config, reduced
    from repro.models import init_params
    from repro.serve import EngineConfig, ModelUpdate, Request, ServeEngine
    from repro.serve.engine import serve_openloop

    cfg = reduced(get_config("mamba2-780m"), n_layers=2, d_model=64)
    n_requests = 8 if quick else 16
    ecfg = EngineConfig(max_slots=4, prompt_len=16, max_new_tokens=12,
                        queue_depth=8, seed=0)

    k_a, k_b, k_prompts = jax.random.split(jax.random.PRNGKey(0), 3)
    params_a = init_params(k_a, cfg)
    params_b = init_params(k_b, cfg)     # the "training made progress" model

    class MidRunSource:
        """Releases model B once the engine has completed half the load —
        the swarm checkpoint that lands mid-serving (load-triggered, not
        wall-clock, so jit warmup can't race the swap past generation 1)."""

        def __init__(self, after_completions):
            self.after = after_completions
            self.engine = None           # bound after engine construction
            self.done = False

        def poll(self):
            if self.done or self.engine is None or \
                    len(self.engine.completions) < self.after:
                return None
            self.done = True
            return ModelUpdate(params_b, 1, time.time(), tag="refresh")

    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (n_requests, ecfg.prompt_len))
    # open-loop Poisson arrivals: exponential gaps, ~25 req/s offered
    gaps = rng.exponential(0.04, n_requests)
    t_arr = np.cumsum(gaps)
    arrivals = [(float(t_arr[i]),
                 Request(i, prompts[i].astype(np.int32)))
                for i in range(n_requests)]

    src = MidRunSource(after_completions=n_requests // 3)
    engine = ServeEngine(cfg, ecfg, params=params_a, source=src)
    src.engine = engine
    completions = serve_openloop(engine, arrivals)
    s = engine.metrics.summary()

    gens = sorted({c.gen for c in completions})
    assert s["swaps_adopted"] >= 2 and len(gens) >= 2, \
        f"no model refresh adopted mid-run: {s} gens={gens}"
    assert s["dropped_in_flight"] == 0, s
    assert s["completed"] + s["rejected"] == n_requests, s
    assert s["decode_cache_misses"] == 0, \
        f"decode step recompiled under swap/churn: {s}"
    out = {"arch": cfg.name, "n_requests": n_requests,
           "engine": {"max_slots": ecfg.max_slots,
                      "prompt_len": ecfg.prompt_len,
                      "max_new_tokens": ecfg.max_new_tokens,
                      "queue_depth": ecfg.queue_depth},
           "generations_served": gens, **s}
    emit("t15_serve/openloop", s["latency_p50_ms"] * 1e3,
         f"tok_s={s['tokens_per_s']};p50_ms={s['latency_p50_ms']};"
         f"p99_ms={s['latency_p99_ms']};qmax={s['queue_depth_max']};"
         f"completed={s['completed']};rejected={s['rejected']}")
    emit("t15_serve/hot_swap", 0.0,
         f"swaps={s['swaps_adopted']};gens={gens};"
         f"fresh_max_s={s['time_to_fresh_max_s']};"
         f"dropped={s['dropped_in_flight']};"
         f"recompiles={s['decode_cache_misses']}")

    # -- paired prefill schedules: head-of-line blocking under a burst ---
    # Same burst (all arrivals at t=0, an attention arch, long prompts),
    # blocking admission vs chunked prefill. The latency series is the
    # per-lane inter-commit gap, so a blocking prefill that stalls every
    # live decode lane lands in the tail; chunked prefill interleaves one
    # [slots, T] chunk per engine step and must STRICTLY cut p99.
    acfg = reduced(get_config("olmo-1b"), n_layers=2, d_model=64)
    aparams = init_params(k_a, acfg)
    n_burst = 8 if quick else 12
    plen = 48

    def burst_run(**kw):
        from repro.serve import ServeMetrics
        e = EngineConfig(max_slots=4, prompt_len=plen, max_new_tokens=12,
                         queue_depth=n_burst, seed=0, **kw)
        eng = ServeEngine(acfg, e, params=aparams)
        bp = rng.integers(0, acfg.vocab_size, (n_burst + 2, plen))
        # warm up every compiled path (prefill/chunk/decode/install) so
        # the measured gaps are steady-state, not first-dispatch compiles
        for w in range(2):
            eng.submit(Request(-1 - w, bp[n_burst + w].astype(np.int32)))
        eng.drain()
        eng.completions.clear()
        kv_b, kv_d = eng.metrics.kv_bytes, eng.metrics.kv_dense_bytes
        eng.metrics = ServeMetrics()
        eng.metrics.kv_bytes, eng.metrics.kv_dense_bytes = kv_b, kv_d
        arr = [(0.0, Request(i, bp[i].astype(np.int32)))
               for i in range(n_burst)]
        serve_openloop(eng, arr)
        ms = eng.metrics.summary()
        assert ms["completed"] == n_burst and \
            ms["dropped_in_flight"] == 0, ms
        return eng, ms

    _, blocking = burst_run()
    _, chunked = burst_run(prefill_chunk=8)
    assert chunked["prefill_cache_misses"] == 0, chunked
    assert chunked["latency_p99_ms"] < blocking["latency_p99_ms"], \
        ("chunked prefill must strictly cut in-flight p99 under bursts",
         blocking["latency_p99_ms"], chunked["latency_p99_ms"])
    out["prefill_paired"] = {
        "arch": acfg.name, "n_burst": n_burst, "prompt_len": plen,
        "blocking": blocking, "chunked": chunked,
        "p99_ratio": round(chunked["latency_p99_ms"] /
                           max(blocking["latency_p99_ms"], 1e-9), 4)}
    emit("t15_serve/prefill_paired", blocking["latency_p99_ms"] * 1e3,
         f"blocking_p99_ms={blocking['latency_p99_ms']};"
         f"chunked_p99_ms={chunked['latency_p99_ms']};"
         f"blocking_ttft_p99_ms={blocking['ttft_p99_ms']};"
         f"chunked_ttft_p99_ms={chunked['ttft_p99_ms']}")

    # -- paged KV pool vs dense bank memory at 50% slot occupancy --------
    # A pool holding HALF the lanes' worth of pages must cost less device
    # memory than the dense full-attention bank — and still serve the
    # whole burst (admissions defer on pool pressure, nothing drops).
    half_pool = EngineConfig(
        max_slots=4, prompt_len=plen, max_new_tokens=12,
        queue_depth=n_burst, seed=0, prefill_chunk=8, paged=True,
        page_size=4)
    half_pool = EngineConfig(
        **{**half_pool.__dict__, "n_pages": 2 * half_pool.pages_per_lane})
    eng_p, paged_s = burst_run(paged=True, page_size=4, prefill_chunk=8,
                               n_pages=half_pool.n_pages)
    assert paged_s["decode_cache_misses"] == 0, paged_s
    assert eng_p.allocator.in_use == 0
    assert 0 < paged_s["kv_bytes"] < paged_s["kv_dense_bytes"], \
        ("paged pool at 50% occupancy must beat the dense bank",
         paged_s["kv_bytes"], paged_s["kv_dense_bytes"])
    out["paged_memory"] = {
        "arch": acfg.name, "page_size": 4,
        "pool_pages": half_pool.pool_pages,
        "kv_bytes": paged_s["kv_bytes"],
        "kv_dense_bytes": paged_s["kv_dense_bytes"],
        "bytes_ratio": round(paged_s["kv_bytes"] /
                             paged_s["kv_dense_bytes"], 4),
        "pool_deferrals": paged_s["pool_deferrals"],
        "completed": paged_s["completed"]}
    emit("t15_serve/paged_memory", 0.0,
         f"pool_bytes={paged_s['kv_bytes']};"
         f"dense_bytes={paged_s['kv_dense_bytes']};"
         f"ratio={out['paged_memory']['bytes_ratio']};"
         f"deferrals={paged_s['pool_deferrals']};"
         f"recompiles={paged_s['decode_cache_misses']}")
    save("t15_serve", out)
    return out


def t16_hier(quick=False):
    """DESIGN.md §Hierarchy: two-tier gossip at equal node count — flat
    8-node vs hier 2x4 (same total nodes, same step budget): trajectory
    quality, host step time, per-tier payload bytes and wall-clock from
    the tiered cost model (predicted-vs-simulated inside a t10-style
    envelope), the q8-compressed resident comm copy's >= 2x state
    reduction, and the 1024-node/512-device dry-run lowering. Emits
    results/bench/t16_hier.json (CI artifact)."""
    import subprocess
    import textwrap

    import jax

    from benchmarks.common import bench_stacked_params
    from repro.configs import get_config, reduced
    from repro.core import bucket as B
    from repro.core.hier import parse_topology
    from repro.quant.codecs import make_codec
    from repro.quant.schemes import ModularQuantConfig
    from repro.sched import (RateProfile, cost_params_from_model,
                             generate_trace, predict_all_modes)

    steps = 8 if quick else 24
    setup = BenchSetup()
    n = setup.n_nodes
    out = {"n_nodes": n, "steps": steps, "topology": "hier:4"}

    # -- flat vs hier at equal node count: trajectory + host step time
    runs = {
        "flat_fp32": dict(),
        "hier_fp32": dict(topology="hier:4"),
        "flat_q8": dict(quantize=True, codec="q8"),
        "hier_q8_compressed": dict(quantize=True, codec="q8",
                                   topology="hier:4", compress_state=True),
    }
    for name, kw in runs.items():
        r = run_steps(setup, "swarm", steps, **kw)
        out[name] = {"final_loss": float(np.mean(r["loss"][-4:])),
                     "final_gamma": r["gamma"][-1],
                     "us_per_step": r["us_per_step"],
                     "compile_s": r["compile_s"]}
        emit(f"t16_hier/{name}", r["us_per_step"],
             f"final_loss={out[name]['final_loss']:.4f};"
             f"gamma={r['gamma'][-1]:.3f}")
    # quality envelope: sharding the swarm must not cost convergence at
    # equal steps (the matching marginals change, the average does not)
    assert out["hier_fp32"]["final_loss"] <= \
        out["flat_fp32"]["final_loss"] * 1.05 + 0.02, out
    assert out["hier_q8_compressed"]["final_loss"] <= \
        out["flat_q8"]["final_loss"] * 1.05 + 0.02, out

    # -- per-tier payload bytes + predicted-vs-simulated wall-clock
    topo = parse_topology("hier:4", n)
    trace = generate_trace(topo.union_graph(),
                           RateProfile("lognormal", sigma=0.5),
                           steps * (n // 2), H=setup.H, h_max=8,
                           seed=setup.seed,
                           edge_weights=topo.edge_weights())
    tiers = topo.tier_of_pairs(trace.pairs)
    out["inter_event_frac"] = float(tiers.mean())
    cfg = reduced(get_config("transformer-wmt"), n_layers=setup.layers,
                  d_model=setup.d_model, vocab=512)
    cost_hier = cost_params_from_model(cfg, seq_len=setup.seq,
                                       local_batch=setup.batch,
                                       quantize=True, codec="q8",
                                       topology="hier:4")
    cost_flat = cost_params_from_model(cfg, seq_len=setup.seq,
                                       local_batch=setup.batch,
                                       quantize=True, codec="q8")
    pred_hier = predict_all_modes(trace, cost_hier, tiers=tiers)
    pred_flat = predict_all_modes(trace, cost_flat)
    out["walltime_tiered"] = pred_hier
    out["walltime_flat_priced"] = pred_flat
    for mode in ("blocking", "nonblocking", "overlap"):
        ratio = pred_hier[mode]["predicted_over_simulated"]
        assert 0.2 <= ratio <= 5.0, (mode, ratio)   # t10-style envelope
    tt = pred_hier["blocking"]["tiers"]
    assert tt["inter"]["comm_time_s"] > tt["intra"]["comm_time_s"]
    emit("t16_hier/tiered_cost", 0.0,
         f"inter_frac={out['inter_event_frac']:.2f};"
         f"intra_B={tt['intra']['bytes']};inter_B={tt['inter']['bytes']};"
         f"sim_hier_s={pred_hier['blocking']['simulated_s']:.4g};"
         f"sim_flat_s={pred_flat['blocking']['simulated_s']:.4g}")

    # -- resident-state shrink: q8 wire prev vs the dense fp32 comm copy
    stacked = bench_stacked_params(n_nodes=n)
    codec = make_codec("q8", ModularQuantConfig())
    layout = B.build_layout(stacked, block=codec.block)
    wire = codec.encode_state(B.pack(layout, stacked),
                              jax.random.PRNGKey(0))
    dense_b = layout.n_padded * 4
    wire_b = sum(int(jax.device_get(w).nbytes) for w in wire) // n
    out["prev_bytes_per_node"] = {"dense_fp32": dense_b, "q8_wire": wire_b,
                                  "reduction_x": dense_b / wire_b}
    assert wire_b * 2 <= dense_b, out["prev_bytes_per_node"]
    emit("t16_hier/state_bytes", 0.0,
         f"dense={dense_b};wire={wire_b};x={dense_b / wire_b:.2f}")

    # -- 1024-node hier:32 swarm lowers on a 512-device mesh (SDS only)
    script = textwrap.dedent("""
        import os, sys
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=512"
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import bucket as B
        from repro.core.swarm import SwarmConfig, SwarmState, make_swarm_step
        from repro.optim import make_optimizer
        from repro.quant.codecs import make_codec
        from repro.quant.schemes import ModularQuantConfig
        NN, D, NDEV = 1024, 4096, 512
        mesh = jax.make_mesh((NDEV,), ("node",))
        scfg = SwarmConfig(n_nodes=NN, H=2, quantize=True, codec="q8",
                           compress_state=True, topology="hier:32",
                           track_potential=False)
        opt = make_optimizer("sgd", lr=0.05, momentum=0.9)
        loss = lambda p, mb: 0.5 * jnp.mean((mb[0] @ p["w"] - mb[1]) ** 2)
        step = make_swarm_step(scfg, loss, opt.update, lambda s: 0.05)
        codec = make_codec("q8", ModularQuantConfig())
        psds = {"w": jax.ShapeDtypeStruct((NN, D), jnp.float32)}
        lay = B.build_layout(psds, block=codec.block)
        prev = codec.wire_layout().wire_sds(NN * lay.rows_per_node)
        msds = {"m": {"w": jax.ShapeDtypeStruct((NN, D), jnp.float32)}}
        st = SwarmState(psds, msds, prev,
                        jax.ShapeDtypeStruct((), jnp.int32))
        node = NamedSharding(mesh, P("node"))
        repl = NamedSharding(mesh, P())
        sh = SwarmState({"w": node}, {"m": {"w": node}},
                        tuple(node for _ in prev), repl)
        jax.jit(step, in_shardings=(sh, (node, node), repl, repl, repl)) \
            .lower(st, (jax.ShapeDtypeStruct((NN, 2, 1, D), jnp.float32),
                        jax.ShapeDtypeStruct((NN, 2, 1), jnp.float32)),
                   jax.ShapeDtypeStruct((NN,), jnp.int32),
                   jax.ShapeDtypeStruct((NN,), jnp.int32),
                   jax.ShapeDtypeStruct((2,), jnp.uint32))
        print("lowered 1")
    """)
    # host-device lowering only: the child must not reach for the chip
    # this (JAX-holding) parent process may own
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=600,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    out["dryrun_1024_nodes_512_devices"] = "lowered 1" in proc.stdout
    assert out["dryrun_1024_nodes_512_devices"]
    emit("t16_hier/dryrun_1024", 0.0, "lowered=ok")
    save("t16_hier", out)
    return out


TABLES = {
    "t1": t1_convergence, "t2": t2_localsteps, "t3": t3_quantization,
    "t4": t4_comm_cost, "t5": t5_potential, "t6": t6_nonblocking,
    "t7": t7_roofline, "t8": t8_topology, "t8_transport": t8_transport,
    "t9": t9_node_scaling, "t9_async": t9_async, "t10_sched": t10_sched,
    "t11_baselines": t11_baselines, "t12_codecs": t12_codecs,
    "t13_fused": t13_fused, "t14_churn": t14_churn, "t15_serve": t15_serve,
    "t16_hier": t16_hier,
}


# One headline metric per t8-t16 table: (artifact, metric name, extractor
# over the saved json). Extractors are defensive — a table that has not
# been run (or an older artifact schema) lands in "missing"/"failed"
# instead of killing the consolidation.
_HEADLINES = [
    ("t8_topology", "complete_final_loss",
     lambda d: float(np.mean(d["complete"]["loss"][-5:]))),
    ("t8_transport", "gather_q8_flat_vs_legacy_speedup",
     lambda d: round(d["gather_q8_legacy"]["us_per_call"] /
                     d["gather_q8_flat"]["us_per_call"], 3)),
    ("t9_node_scaling", "final_loss_max_nodes",
     lambda d: float(np.mean(
         d[max(d, key=lambda k: int(k))]["loss"][-5:]))),
    ("t9_async", "paired_median_blocking_minus_overlap_us",
     lambda d: d["paired_median_blocking_minus_overlap_us"]),
    ("t10_sched", "lognormal_final_loss",
     lambda d: d["lognormal"]["final_loss"]),
    ("t11_baselines", "swarm_q8_final_loss",
     lambda d: d["swarm_q8"]["final_loss"]),
    ("t12_codecs", "q8_payload_ratio",
     lambda d: round(d["measured_payload"]["q8_payload_bytes"] /
                     d["measured_payload"]["fp32_payload_bytes"], 4)),
    ("t13_fused", "q8_scan_speedup", lambda d: d["q8"]["scan_speedup"]),
    ("t14_churn", "final_loss_under_churn", lambda d: d["final_loss"]),
    ("t15_serve", "tokens_per_s", lambda d: d["tokens_per_s"]),
    ("t15_serve", "latency_p99_ms", lambda d: d["latency_p99_ms"]),
    ("t15_serve", "chunked_prefill_p99_ratio",
     lambda d: d["prefill_paired"]["p99_ratio"]),
    ("t15_serve", "paged_kv_bytes_ratio",
     lambda d: d["paged_memory"]["bytes_ratio"]),
    ("t16_hier", "hier_fp32_final_loss",
     lambda d: d["hier_fp32"]["final_loss"]),
]


def summarize():
    """Consolidate the per-table artifacts into results/bench/summary.json:
    one row per t8-t16 headline metric (the numbers README quotes), so CI
    uploads a single machine-readable file next to the raw tables."""
    rows, missing, failed = [], [], []
    cache = {}
    for table, metric, fn in _HEADLINES:
        path = os.path.join(OUT, table + ".json")
        if table not in cache:
            if not os.path.exists(path):
                missing.append(table)
                cache[table] = None
            else:
                with open(path) as f:
                    cache[table] = json.load(f)
        if cache[table] is None:
            continue
        try:
            rows.append({"table": table, "metric": metric,
                         "value": fn(cache[table]), "source": path})
        except (KeyError, TypeError, ValueError, ZeroDivisionError) as e:
            failed.append({"table": table, "metric": metric,
                           "error": repr(e)})
    summary = {"rows": rows, "missing": sorted(set(missing)),
               "failed": failed}
    save("summary", summary)
    for r in rows:
        emit(f"summary/{r['table']}.{r['metric']}", 0.0,
             f"value={r['value']}")
    if missing or failed:
        emit("summary/incomplete", 0.0,
             f"missing={sorted(set(missing))};failed={len(failed)}")
    return summary


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--summary", action="store_true",
                    help="consolidate existing results/bench/*.json into "
                         "summary.json (one row per t8-t16 headline "
                         "metric); runs after any tables selected")
    args = ap.parse_args()
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    names = args.only.split(",") if args.only else list(TABLES)
    if args.summary and args.only is None:
        names = []                     # bare --summary: consolidate only
    print("name,us_per_call,derived")
    for n in names:
        TABLES[n](quick=args.quick)
    if args.summary:
        summarize()


if __name__ == "__main__":
    main()
