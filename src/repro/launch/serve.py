"""Serving driver: one-shot batched generation (the oracle path) plus the
continuous-batching modes over live swarm models (DESIGN.md §Serving).

One-shot (oracle): prefill a prompt batch, then decode tokens with a KV
cache (greedy or temperature sampling). CPU-runnable at reduced scale; the
same serve_step is what the dry-run lowers for decode_32k / long_500k.

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-780m --reduced \
      --batch 2 --prompt-len 32 --gen 16

Continuous batching (serve/engine.py) with hot model swap:

  # follow a (possibly still running) training run's checkpoint dir
  ... -m repro.launch.serve --arch mamba2-780m --reduced \
      --source follow --follow runs/swarm --nodes 8 --requests 8

  # serve an in-process live swarm (training loop publishes snapshots)
  ... -m repro.launch.serve --arch mamba2-780m --reduced --source live \
      --nodes 4 --live-steps 6 --requests 6
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced
from repro.launch.cache import use_compile_cache
from repro.models import forward, init_cache, init_params
from repro.models.multimodal import synth_prefix_embeds
from repro.models.transformer import logits_head


def make_serve_fns(cfg):
    @jax.jit
    def prefill(params, tokens, prefix_embeds=None):
        hidden, cache, _ = forward(cfg, params, tokens, mode="prefill",
                                   prefix_embeds=prefix_embeds)
        return logits_head(cfg, params, hidden[:, -1:]), cache

    @jax.jit
    def decode_step(params, cache, tokens):
        hidden, cache, _ = forward(cfg, params, tokens, mode="decode",
                                   cache=cache)
        return logits_head(cfg, params, hidden), cache

    return prefill, decode_step


def sample_token(logits, key, temperature: float):
    if temperature <= 0:
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits[:, -1] / temperature).astype(jnp.int32)


def run_oneshot(cfg, args, params, keys):
    """The one-shot batched path — kept verbatim as the serving oracle the
    engine's tests compare against."""
    from repro.serve.engine import grow_cache
    prefill, decode_step = make_serve_fns(cfg)

    prompts = jax.random.randint(keys["prompts"], (args.batch,
                                 args.prompt_len), 0, cfg.vocab_size)
    prefix = None
    if cfg.frontend is not None:
        prefix = synth_prefix_embeds(keys["prefix"], cfg, args.batch)

    t0 = time.time()
    logits, cache = prefill(params, prompts, prefix)
    # grow the KV cache to prompt+gen capacity (raises on any structural
    # mismatch — serve/engine.py)
    total = args.prompt_len + args.gen + (
        cfg.frontend.n_prefix if cfg.frontend is not None else 0)
    cache = grow_cache(init_cache(cfg, args.batch, total), cache)
    t_prefill = time.time() - t0

    key = keys["sample"]
    key, sub = jax.random.split(key)
    tok = sample_token(logits, sub, args.temperature)[:, None]
    out = [np.asarray(tok)]
    t0 = time.time()
    for _ in range(args.gen - 1):
        key, sub = jax.random.split(key)
        logits, cache = decode_step(params, cache, tok)
        tok = sample_token(logits, sub, args.temperature)[:, None]
        out.append(np.asarray(tok))
    jax.block_until_ready(tok)
    t_decode = time.time() - t0
    gen = np.concatenate(out, axis=1)
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} "
          f"gen={args.gen}")
    print(f"prefill {t_prefill*1e3:.1f} ms; decode "
          f"{t_decode/max(args.gen-1,1)*1e3:.2f} ms/token")
    print("generated tokens[0,:16]:", gen[0, :16].tolist())


def _make_requests(cfg, args, key):
    from repro.serve import Request
    prompts = jax.random.randint(
        key, (args.requests, args.prompt_len), 0, cfg.vocab_size)
    prompts = np.asarray(prompts, np.int32)
    gap = args.arrival_gap_ms / 1e3
    return [(i * gap, Request(i, prompts[i])) for i in range(args.requests)]


def _engine_cfg(args):
    """EngineConfig from CLI args; paged/chunked knobs left at None fall
    through to the EngineConfig env-var defaults (REPRO_SERVE_PAGED,
    REPRO_SERVE_PAGE_SIZE, REPRO_PREFILL_CHUNK)."""
    from repro.serve import EngineConfig
    kw = dict(max_slots=args.slots, prompt_len=args.prompt_len,
              max_new_tokens=args.gen, queue_depth=args.queue_depth,
              temperature=args.temperature, seed=args.seed)
    for name, val in (("paged", args.paged),
                      ("page_size", args.page_size),
                      ("n_pages", args.kv_pages),
                      ("prefill_chunk", args.prefill_chunk)):
        if val is not None:
            kw[name] = val
    return EngineConfig(**kw)


def run_continuous(cfg, args, keys, *, source, params=None):
    from repro.serve import ServeEngine
    from repro.serve.engine import serve_openloop
    ecfg = _engine_cfg(args)
    engine = ServeEngine(cfg, ecfg, params=params, source=source)
    # block until the source delivers a first model (a follower pointed at
    # a run dir that hasn't checkpointed yet)
    deadline = time.time() + args.wait_s
    while engine.swap.latest() is None:
        engine.poll_source()
        if engine.swap.latest() is not None:
            break
        if time.time() > deadline:
            raise TimeoutError(
                f"no model from source after {args.wait_s}s "
                f"(--source {args.source})")
        time.sleep(0.05)
    completions = serve_openloop(engine, _make_requests(
        cfg, args, keys["prompts"]))
    summary = engine.metrics.summary()
    print(json.dumps({"serve": summary}))
    for c in completions[: min(4, len(completions))]:
        print(f"rid={c.rid} gen={c.gen} tokens[:8]="
              f"{c.tokens[:8].tolist()}")
    return completions, summary


def run_live(cfg, args, keys):
    """Serve an in-process live swarm: a real (reduced) training loop is
    the producer, publishing the swarm mean through LiveSource at every
    superstep; the engine consumes snapshots between decode steps."""
    from repro.data.synthetic import DataConfig, SyntheticLMDataset, \
        make_node_batches
    from repro.launch.train import build_trainer, presample_inputs
    from repro.serve import LiveSource

    seq = 32
    step, state, scfg, graph = build_trainer(
        cfg, "swarm", args.nodes, 1, 0.05, False, False, "complete",
        args.seed, "fixed")
    src = LiveSource(_transport(scfg, graph, args.seed))
    ds = SyntheticLMDataset(
        DataConfig(vocab_size=cfg.vocab_size, seq_len=seq, seed=args.seed),
        n_nodes=args.nodes)
    rng_np = np.random.default_rng(args.seed)
    perms, hs = presample_inputs(scfg, graph, rng_np, args.seed,
                                 args.live_steps, True)
    key = keys["train"]
    h_max = scfg.h_loop_bound
    src.publish(state.params)

    def train_some(n):
        nonlocal state, key
        t0 = len(train_some.done)
        for t in range(t0, min(t0 + n, args.live_steps)):
            nb = make_node_batches(ds, t, args.batch * h_max)
            batch = {k: jnp.asarray(
                v.reshape(args.nodes, h_max, args.batch, seq))
                for k, v in nb.items()}
            key, sub = jax.random.split(key)
            state, _ = step(state, batch, jnp.asarray(perms[t]),
                            jnp.asarray(hs[t]), sub)
            src.publish(state.params)
            train_some.done.append(t)
    train_some.done = []

    # interleave: a few supersteps, then serve a request wave, repeat
    from repro.serve import ServeEngine
    engine = ServeEngine(cfg, _engine_cfg(args), source=src)
    reqs = _make_requests(cfg, args, keys["prompts"])
    waves = max(1, args.live_steps // 2)
    per = max(1, len(reqs) // waves)
    done = []
    for w in range(0, len(reqs), per):
        train_some(2)
        for _, r in reqs[w:w + per]:
            engine.submit(r)
        engine.drain()
    done = engine.completions
    summary = engine.metrics.summary()
    print(json.dumps({"serve": summary}))
    gens = sorted({c.gen for c in done})
    print(f"served {len(done)} requests across model generations {gens}")
    return done, summary


def _transport(scfg, graph, seed):
    from repro.core.exchange import transport_from_config
    return transport_from_config(scfg, graph, seed)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-780m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    # model source (DESIGN.md §Serving)
    ap.add_argument("--source", choices=["oneshot", "follow", "live"],
                    default="oneshot",
                    help="oneshot: random-init batch generation (oracle); "
                         "follow: continuous batching over a run dir's "
                         "checkpoints; live: serve an in-process swarm")
    ap.add_argument("--follow", default=None, metavar="RUNDIR",
                    help="checkpoint dir to follow (implies "
                         "--source follow)")
    ap.add_argument("--weights", default=None,
                    help="serving checkpoint (export_serving_checkpoint) "
                         "to seed the model from")
    ap.add_argument("--nodes", type=int, default=4,
                    help="swarm width of the followed/live run")
    # engine knobs
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--queue-depth", type=int, default=8)
    ap.add_argument("--paged", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="paged KV cache (serve/paged.py); default: "
                         "REPRO_SERVE_PAGED env (off)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="KV rows per page; default: REPRO_SERVE_PAGE_SIZE "
                         "env (8)")
    ap.add_argument("--kv-pages", type=int, default=None,
                    help="global page-pool size; 0 = every lane at full "
                         "capacity (no saving, no deferral)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="tokens per prefill chunk (0 = blocking "
                         "admission); default: REPRO_PREFILL_CHUNK env (0)")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--arrival-gap-ms", type=float, default=10.0)
    ap.add_argument("--wait-s", type=float, default=30.0)
    ap.add_argument("--live-steps", type=int, default=6)
    args = ap.parse_args()
    use_compile_cache()
    if args.follow:
        args.source = "follow"

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg, n_layers=args.layers, d_model=args.d_model)

    # RNG hygiene: independent streams for init / prompts / prefix /
    # sampling / live-training (the historical driver reused ONE key for
    # all four, correlating prompts with weights)
    rng = jax.random.PRNGKey(args.seed)
    k_init, k_prompts, k_prefix, k_sample, k_train = jax.random.split(rng, 5)
    keys = {"init": k_init, "prompts": k_prompts, "prefix": k_prefix,
            "sample": k_sample, "train": k_train}

    if args.source == "live":
        run_live(cfg, args, keys)
        return
    params = None
    if args.weights:
        from repro.serve import load_serving_checkpoint
        like = jax.eval_shape(lambda k: init_params(k, cfg), keys["init"])
        params = load_serving_checkpoint(args.weights, like)
    if args.source == "oneshot":
        if params is None:
            params = init_params(keys["init"], cfg)
        run_oneshot(cfg, args, params, keys)
        return
    # --source follow
    from repro.serve import CheckpointFollower
    like = jax.eval_shape(lambda k: init_params(k, cfg), keys["init"])
    follower = CheckpointFollower(args.follow, like, args.nodes)
    run_continuous(cfg, args, keys, source=follower, params=params)


if __name__ == "__main__":
    main()
