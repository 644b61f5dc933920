"""Bring-up smoke run of both jobs of the system on a TPU, at published widths.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: cross-chip gossip only

One chip runs three phases in one process:

* kernels: the Pallas codec and optimizer kernels against their jnp
  oracle (kernels/ref.py) on a small input, on the chip;
* train: `repro.launch.train.build_trainer` and its per-step loop on
  transformer-wmt (12 layers, d_model 1024, d_ff 4096, vocab 32768, bf16,
  random init), SwarmSGD with the q8 lattice wire, the swarm's nodes
  vmapped on the chip, 3 supersteps; the compiled superstep must hold the
  quantize, decode-average and SGD kernels as TPU custom calls;
* serve: the continuous-batching `ServeEngine` that `repro.launch.serve`
  drives, on olmo-1b (16 layers, d_model 2048, random init) with paged KV
  and chunked prefill — two waves of requests, every one completed, no
  recompiles after the first wave.

`--chips 4` runs only the cross-chip path and what it is compared with:
transformer-wmt with one node per chip over the `ppermute_pool` transport
(fp32 and q8 wire), against the same seed with all nodes on one device
through the `gather` transport.

Earlier lines of stdout report what was found; the last line is one JSON
object `{"ok": true, "device": {"platform", "kind", "count"}}`. Without a
TPU, without the repo's `src/` next to this file, or when any check fails,
the script exits non-zero and prints no such line. The compile cache lives
where `JAX_COMPILATION_CACHE_DIR` says, else in `<repo>/.jax_cache`.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
TRAIN_ARCH, SERVE_ARCH = "transformer-wmt", "olmo-1b"
SEED = 0
# one chip: 4 vmapped nodes do not fit its 15.75 GiB of HBM (the fused
# optimizer packs params, grads and momentum of every node to fp32 flat
# buffers at once; compile rehearsal for a described v5e: 19.26 GiB for
# 4 nodes, 12.4 GiB for 2), 2 nodes do
TRAIN_NODES_1CHIP = 2
STEPS, SEQ, LOCAL_BATCH, H, LR = 3, 128, 4, 2, 0.05
FOUR_CHIP_LAYERS = 2
POOL_SIZE = 3                    # the three perfect matchings of 4 nodes
FP32_RTOL = 1e-5                 # pool vs gather, exact fp32 wire
Q8_ATOL = 0.05                   # pool vs gather, q8 wire (tests' bound)


def log(**kw):
    print(json.dumps(kw), flush=True)


def check(cond: bool, what: str):
    if not cond:
        raise AssertionError(what)


def peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


def kernel_phase(jax, jnp, np):
    """Pallas kernels (the platform default here) against the jnp oracle
    on a small flat buffer. The chip's f32 division may round differently
    in the two paths, so a code may move by one lattice step: the check
    is that wire codes agree to within one step (mod 2^bits) and the
    decoded average to within half a scale."""
    from repro.kernels import decode_avg, quantize_mod, sgd_fused_update
    from repro.kernels.ops import resolve_backend
    check(resolve_backend(None) == "pallas",
          "a TPU run must default to the Pallas kernels")
    rng = np.random.default_rng(SEED)
    n = 64 * 256
    x = jnp.asarray(rng.normal(size=n), jnp.float32)
    ref = x + jnp.asarray(0.01 * rng.normal(size=n), jnp.float32)
    u = jnp.asarray(rng.uniform(size=n), jnp.float32)
    m = jnp.asarray(rng.random(64) < 0.5)
    out = {}
    for bits, pack4 in ((8, False), (4, True), (16, False)):
        res = {}
        for be in (None, "ref"):
            q, s, _ = jax.jit(lambda a, b, c, be=be: quantize_mod(
                a, b, c, bits=bits, pack4=pack4, backend=be))(x, ref, u)
            d = jax.jit(lambda q, s, y, mm, be=be: decode_avg(
                q, s, y, bits=bits, matched=mm, pack4=pack4,
                backend=be))(q, s, ref, m)
            res[be] = [np.asarray(a) for a in (q, s, d)]
        (qp, sp, dp), (qr, sr, dr) = res[None], res["ref"]
        if pack4:
            qp = np.concatenate([qp & 15, qp >> 4], axis=-1)
            qr = np.concatenate([qr & 15, qr >> 4], axis=-1)
        step = (qp.astype(np.int64) - qr.astype(np.int64)) % (1 << bits)
        check(bool(np.isin(step, (0, 1, (1 << bits) - 1)).all()),
              f"q{bits}: Pallas codes off the oracle by more than one step")
        check(np.allclose(sp, sr, rtol=1e-6, atol=0),
              f"q{bits}: Pallas scales differ from the oracle")
        check(bool((np.abs(dp - dr) <= 0.5 * sr.max() + 1e-6).all()),
              f"q{bits}: Pallas decode differs from the oracle")
        out[f"q{bits}{'_pack4' if pack4 else ''}_code_match"] = \
            float((step == 0).mean())
    p, g = x, ref
    mom = jnp.asarray(0.1 * rng.normal(size=n), jnp.float32)
    a = jax.jit(lambda p, g, mm: sgd_fused_update(
        p, g, mm, lr=0.05, mu=0.9, wd=0.01))(p, g, mom)
    b = jax.jit(lambda p, g, mm: sgd_fused_update(
        p, g, mm, lr=0.05, mu=0.9, wd=0.01, backend="ref"))(p, g, mom)
    for got, want in zip(a, b):
        check(np.allclose(np.asarray(got), np.asarray(want), rtol=1e-6,
                          atol=1e-6), "sgd_update differs from the oracle")
    log(phase="kernels", ok=True, **out)


def train_run(jax, jnp, np, cfg, *, n_nodes, devices, impl, quantize,
              perms=None):
    """build_trainer + the per-step loop of repro.launch.train (no
    scheduler, fixed H): returns (losses, compiled superstep, state,
    compile_s, run_s, pool index stream)."""
    from repro.data import DataConfig, SyntheticLMDataset, make_node_batches
    from repro.launch.mesh import node_mesh
    from repro.launch.train import (build_trainer, place_nodes,
                                    presample_inputs)
    step, state, scfg, graph = build_trainer(
        cfg, "swarm", n_nodes, H, LR, quantize=quantize, seed=SEED,
        gossip_impl=impl, pool_size=POOL_SIZE, devices=devices)
    mesh = node_mesh(n_nodes, devices)
    ds = SyntheticLMDataset(
        DataConfig(vocab_size=cfg.vocab_size, seq_len=SEQ, seed=SEED),
        n_nodes=n_nodes)
    sampled, hs = presample_inputs(scfg, graph, np.random.default_rng(SEED),
                                   SEED, STEPS)
    perms = sampled if perms is None else perms
    h_max = scfg.h_loop_bound

    def batch(t):
        nb = make_node_batches(ds, t, LOCAL_BATCH * h_max)
        return place_nodes({k: v.reshape(n_nodes, h_max, LOCAL_BATCH, SEQ)
                            for k, v in nb.items()}, mesh)

    key = jax.random.PRNGKey(SEED + 1)
    t0 = time.time()
    compiled = step.lower(state, batch(0), jnp.asarray(perms[0]),
                          jnp.asarray(hs[0]), key).compile()
    compile_s = time.time() - t0
    losses = []
    t0 = time.time()
    for t in range(STEPS):
        key, sub = jax.random.split(key)
        state, m = compiled(state, batch(t), jnp.asarray(perms[t]),
                            jnp.asarray(hs[t]), sub)
        losses.append(float(m["loss"]))      # blocks on the step
    run_s = time.time() - t0
    return losses, compiled, state, compile_s, run_s, sampled


def kernel_calls(hlo: str):
    names = re.findall(r"%([A-Za-z_]+?)(?:\.\d+)? = [^\n]*"
                       r"custom_call_target=\"tpu_custom_call\"", hlo)
    return len(names), sorted(set(names))


def train_phase(jax, jnp, np, dev):
    from repro.configs import get_config
    cfg = get_config(TRAIN_ARCH)
    check((cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.dtype)
          == (12, 1024, 4096, 32768, "bfloat16"),
          f"{TRAIN_ARCH} is not at its published width")
    losses, compiled, state, compile_s, run_s, _ = train_run(
        jax, jnp, np, cfg, n_nodes=TRAIN_NODES_1CHIP, devices=[dev],
        impl="gather", quantize=True)
    n_calls, names = kernel_calls(compiled.as_text())
    check(all(math.isfinite(x) for x in losses), f"loss not finite: {losses}")
    check(len(losses) >= 3, "fewer than 3 supersteps")
    check({"quantize_mod", "decode_avg", "sgd_update"} <= set(names),
          f"superstep lacks a kernel as tpu_custom_call: {names}")
    for leaf in jax.tree.leaves(state.params):
        check(bool(jnp.isfinite(leaf.astype(jnp.float32)).all()),
              "non-finite parameter after training")
    log(phase="train", ok=True, arch=TRAIN_ARCH,
        layers=cfg.n_layers, d_model=cfg.d_model, d_ff=cfg.d_ff,
        vocab=cfg.vocab_size, dtype=cfg.dtype, nodes=TRAIN_NODES_1CHIP,
        wire="q8", seq=SEQ, local_batch=LOCAL_BATCH, H=H,
        supersteps=len(losses), losses=losses, compile_s=compile_s,
        run_s=run_s, tpu_custom_calls=n_calls, kernels=names,
        peak_bytes=peak_bytes(dev))


def serve_phase(jax, jnp, np, dev):
    from repro.configs import get_config
    from repro.launch.serve import _engine_cfg, _make_requests
    from repro.models import init_params
    from repro.serve import ServeEngine
    from repro.serve.engine import serve_openloop
    cfg = get_config(SERVE_ARCH)
    check((cfg.n_layers, cfg.d_model) == (16, 2048),
          f"{SERVE_ARCH} is not at its published width")
    args = argparse.Namespace(
        slots=4, prompt_len=128, gen=16, queue_depth=8, temperature=0.0,
        seed=SEED, paged=True, page_size=16, kv_pages=None,
        prefill_chunk=32, requests=4, arrival_gap_ms=0.0, wait_s=30.0)
    keys = dict(zip(("init", "prompts"),
                    jax.random.split(jax.random.PRNGKey(SEED))))
    params = init_params(keys["init"], cfg)
    # the engine launch/serve.py builds; wave 1 compiles, wave 2 must not
    engine = ServeEngine(cfg, _engine_cfg(args), params=params)
    waves = []
    for wave in range(2):
        t0 = time.time()
        done = serve_openloop(engine, _make_requests(
            cfg, args, jax.random.fold_in(keys["prompts"], wave)))
        waves.append(time.time() - t0)
        summary = engine.metrics.summary()
        want = args.requests * (wave + 1)
        check(summary["completed"] == want and len(done) == want,
              f"wave {wave}: {summary['completed']}/{want} done")
        check(summary["dropped_in_flight"] == 0, "dropped requests")
        check(summary["decode_cache_misses"] == 0, "decode recompiled")
        check(summary["prefill_cache_misses"] == 0, "prefill recompiled")
        for c in done:
            toks = np.asarray(c.tokens)
            check(toks.shape == (args.gen,), f"request {c.rid}: "
                  f"{toks.shape[0]} of {args.gen} tokens")
            check(bool(((toks >= 0) & (toks < cfg.vocab_size)).all()),
                  f"request {c.rid}: token out of vocabulary")
    log(phase="serve", ok=True, arch=SERVE_ARCH, layers=cfg.n_layers,
        d_model=cfg.d_model, requests=args.requests, slots=args.slots,
        prompt_len=args.prompt_len, new_tokens=args.gen,
        first_wave_s=waves[0], second_wave_s=waves[1], summary=summary,
        peak_bytes=peak_bytes(dev))


def four_chip_phase(jax, jnp, np, devs):
    """One node per chip over ppermute_pool vs the same seed with every
    node on one device through gather, fp32 and q8 wires."""
    from repro.configs import get_config
    from repro.core.exchange import make_matching_pool
    from repro.core.graph import make_graph
    # published widths, depth cut: the one-device reference holds all
    # four fp32 nodes on one chip, which 12 layers overflow (see above)
    # and 4 nearly fill (compile rehearsal: 13.8 GiB). fp32
    # parameters: in bf16 one reduction-order difference between the
    # sharded and the vmapped program flips a parameter by 2^-8, which
    # would measure rounding, not the transport
    cfg = dataclasses.replace(get_config(TRAIN_ARCH),
                              n_layers=FOUR_CHIP_LAYERS, dtype="float32")
    n = len(devs)
    pool = make_matching_pool(make_graph("complete", n), K=POOL_SIZE,
                              seed=SEED)
    for quantize in (False, True):
        wire = "q8" if quantize else "fp32"
        lp, compiled, state, c_pool, r_pool, idx = train_run(
            jax, jnp, np, cfg, n_nodes=n, devices=devs,
            impl="ppermute_pool", quantize=quantize)
        hlo = compiled.as_text()
        shards = [leaf.addressable_shards
                  for leaf in jax.tree.leaves(state.params)]
        one_node_each = all(
            len(s) == n and {x.device for x in s} == set(devs)
            and all(x.data.shape[0] == 1 for x in s) for s in shards)
        del compiled, state
        gc.collect()
        gather_perms = [pool[int(row[0])] for row in idx]
        lg, compiled, state, c_g, r_g, _ = train_run(
            jax, jnp, np, cfg, n_nodes=n, devices=devs[:1], impl="gather",
            quantize=quantize, perms=gather_perms)
        del compiled, state
        gc.collect()
        diffs = [abs(a - b) for a, b in zip(lp, lg)]
        if quantize:
            agree = max(diffs) <= Q8_ATOL
        else:
            agree = all(d <= FP32_RTOL * abs(b) for d, b in zip(diffs, lg))
        log(phase=f"four_chip_{wire}", arch=TRAIN_ARCH,
            layers=cfg.n_layers, d_model=cfg.d_model, dtype=cfg.dtype,
            nodes=n,
            pool_losses=lp, gather_losses=lg,
            max_abs_diff=max(diffs), losses_agree=agree,
            collective_permutes=hlo.count("collective-permute"),
            one_node_per_device=one_node_each, pool_compile_s=c_pool,
            pool_run_s=r_pool, gather_compile_s=c_g, gather_run_s=r_g,
            peak_bytes=[peak_bytes(d) for d in devs])
        check(all(math.isfinite(x) for x in lp + lg), "loss not finite")
        check(agree, f"{wire}: pool and gather losses disagree: {diffs}")
        check("collective-permute" in hlo,
              f"{wire}: no collective-permute in the pool superstep")
        check(one_node_each, f"{wire}: state not one node per device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cross-chip gossip comparison")
    args = ap.parse_args(argv)
    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.cache import use_compile_cache
    cache_dir = use_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 3
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} device(s)",
              file=sys.stderr)
        return 4
    log(device_kind=dev.device_kind, platform=dev.platform,
        count=len(devs), jax=jax.__version__, compile_cache=cache_dir)
    t0 = time.time()
    if args.chips == 4:
        four_chip_phase(jax, jnp, np, devs[:4])
    else:
        kernel_phase(jax, jnp, np)
        train_phase(jax, jnp, np, dev)
        gc.collect()                  # the swarm state dies before serving
        serve_phase(jax, jnp, np, dev)
    log(total_s=time.time() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
