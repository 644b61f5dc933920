"""Serving job: the program's continuous-batching engine
(`repro.serve.ServeEngine.step`) under open-loop traffic.

Set-up makes the weights on the device from the seed, builds the engine
as the configuration states it (paged KV, chunked prefill, greedy), and
compiles its programs with one short request per slot. The traffic's
requests then arrive by a Poisson schedule fixed in the traffic file:
each is stamped with its due time before it is submitted, so a request
that waits for an engine step has its wait counted. The window opens
after a warm-up at the same rate, and closes after `--seconds`.

* `serve_tokens_per_s`: output tokens committed in the window, over it;
* `ttft_p95_ms`: over every request due in the window, due time to first
  token; a rejected request, or one never served, counts as missing;
* `itl_p95_ms`: every gap between consecutive output tokens committed in
  the window.

After the window no request is sent; the engine finishes those due in it
(for at most `drain_s`). A sample of them drawn from the seed, with the
longest, is then replayed through the plain float32 reference that the
configuration names (bench/reference): the gap by which each served
token's reference logit lies below the reference's best is the number
compared.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench.harness import model_config
from bench.stats import peak_bytes, percentile
from bench.trace import span
from bench.traffic import chat_requests, seed32

MISSING_MS = 3.6e6          # a request never served counts as an hour
REFERENCE_NEEDS = ("init_params", "hidden", "logits")


class Server:
    """The engine with its weights, driven by one traffic schedule."""

    def __init__(self, cell, device, seed: int):
        import jax
        import jax.numpy as jnp
        from repro.models import init_params
        from repro.serve.engine import EngineConfig, ServeEngine
        self.cfg = cfg = model_config(cell.config)
        self.ecfg = EngineConfig(**cell.config["deployment"]["engine"])
        shapes = jax.eval_shape(
            lambda: init_params(jax.random.PRNGKey(0), cfg))
        dtype = jnp.dtype(cfg.dtype)
        ref, weights = cell.reference, cell.config.get("weights", {})
        self.make_weights = jax.jit(
            lambda key: ref.init_params(key, shapes, dtype, **weights),
            out_shardings=jax.sharding.SingleDeviceSharding(device))
        self.seed = seed
        self.engine = ServeEngine(cfg, self.ecfg, params=self.weights())

    def weights(self):
        import jax
        return self.make_weights(jax.random.PRNGKey(seed32(self.seed)))

    def prewarm(self):
        """One short request per slot: compiles every program the traffic
        uses (chunked prefill, decode, lane reset, page-table updates)."""
        from repro.serve.engine import Request
        eng = self.engine
        n0 = len(eng.completions)
        for i in range(self.ecfg.max_slots):
            eng.submit(Request(rid=-1 - i, prompt=np.arange(
                1, self.ecfg.prefill_chunk + 2, dtype=np.int32),
                max_new_tokens=2))
        eng.drain()
        del eng.completions[n0:]

    def drive(self, requests, warmup_s: float, seconds: float, drain_s: float,
              ctx=None) -> dict:
        """Send `requests` ((offset, prompt, budget), offsets from now) by
        their schedule; measure the window [warmup_s, warmup_s + seconds)."""
        from repro.serve.engine import Request
        eng, met = self.engine, self.engine.metrics
        t_origin = time.time()
        due = [t_origin + r[0] for r in requests]
        accepted, i, n = {}, 0, len(requests)
        t_w0 = t_w1 = None
        while True:
            now = time.time()
            if i < n and due[i] <= now:
                with span("serve.generate"):
                    while i < n and due[i] <= now:
                        _, prompt, budget = requests[i]
                        accepted[i] = eng.submit(Request(
                            rid=i, prompt=prompt, max_new_tokens=budget,
                            t_submit=due[i]))
                        i += 1
            if t_w0 is None and now >= t_origin + warmup_s:
                t_w0 = ctx.begin_window() if ctx else time.time()
                tok0, gap0 = met.tokens_committed, len(met.token_latencies_s)
            if t_w0 is not None and now >= t_w0 + seconds:
                t_w1 = ctx.end_window() if ctx else time.time()
                tok1, gap1 = met.tokens_committed, len(met.token_latencies_s)
                break
            if not eng.queue and not eng.active_count:
                with span("serve.wait"):
                    nxt = due[i] if i < n else now + 1e-3
                    time.sleep(max(0.0, min(nxt - now, 1e-3)))
                continue
            with span("serve.step"):
                eng.step()
        in_win = [k for k in range(n) if t_w0 <= due[k] < t_w1]
        t_stop = time.time() + drain_s
        done = {}
        want = {k for k in in_win if accepted.get(k)}
        while time.time() < t_stop:
            done = {c.rid: c for c in eng.completions if c.rid in want}
            if len(done) == len(want):
                break
            eng.step()
        return {"t_w0": t_w0, "t_w1": t_w1, "tokens": tok1 - tok0,
                "gaps_s": met.token_latencies_s[gap0:gap1],
                "in_window": in_win, "accepted": accepted, "done": done,
                "due": due, "requests": requests}

    def free(self):
        self.engine = None
        gc.collect()


def window_stats(d: dict) -> dict:
    """End-to-end numbers and failures of one driven window."""
    ttft, qwait, failed = [], [], 0
    for k in d["in_window"]:
        c = d["done"].get(k)
        budget = d["requests"][k][2]
        if c is None or len(c.tokens) != budget:
            failed += 1
            ttft.append(MISSING_MS)
            continue
        ttft.append(1e3 * (c.t_first_token - d["due"][k]))
        qwait.append(1e3 * (c.t_admit - d["due"][k]))
    win = d["t_w1"] - d["t_w0"]
    return {
        "window_s": win,
        "serve_tokens_per_s": d["tokens"] / win,
        "ttft_p95_ms": percentile(ttft, 95),
        "itl_p95_ms": 1e3 * percentile(d["gaps_s"], 95),
        "queue_wait_p95_ms": percentile(qwait, 95),
        "attempted": len(d["in_window"]), "failed": failed,
        "rejected": sum(1 for k in d["in_window"]
                        if not d["accepted"].get(k)),
        "tokens": d["tokens"]}


def sample_for_check(d: dict, seed: int, min_tokens: int) -> list:
    """Finished window requests to replay: the longest, then others drawn
    from the seed until `min_tokens` served tokens are in the sample."""
    ks = sorted(d["done"], key=lambda k: (len(d["requests"][k][1])
                                          + len(d["done"][k].tokens), k))
    if not ks:
        return []
    out = [ks[-1]]
    rest = list(np.random.default_rng(seed32(seed) + 7).permutation(ks[:-1]))
    while rest and sum(len(d["done"][k].tokens) for k in out) < min_tokens:
        out.append(int(rest.pop()))
    return out


def served_gaps(ref, model: dict, params, sample, d: dict, pad_to: int,
                rows: int, prec: str = "f32") -> np.ndarray:
    """For each sampled request, replay prompt + served tokens through the
    reference module `ref` with the configuration's `model`; per served
    token, how far its reference logit lies below the reference's best.
    With prec="fp8" the control: the gap of the token the lower-precision
    reference puts first."""
    import jax
    from bench.reference.serve import gap_fn
    fn = gap_fn(ref, model, pad_to, rows, prec)
    out = []
    for k in sample:
        prompt = d["requests"][k][1]
        toks = np.asarray(d["done"][k].tokens, np.int32)
        seq = np.zeros((pad_to,), np.int32)
        seq[:len(prompt)] = prompt
        seq[len(prompt):len(prompt) + len(toks)] = toks
        tgt = np.zeros((rows,), np.int32)
        tgt[:len(toks)] = toks
        g = np.asarray(fn(params, seq, len(prompt) - 1, tgt))[:len(toks)]
        out.append(g)
    return np.concatenate(out) if out else np.zeros((0,))


def run(ctx) -> dict:
    cell, tr = ctx.cell, ctx.cell.traffic
    dev = ctx.devices[0]
    srv = Server(cell, dev, ctx.seed)
    ctx.phase("weights_and_engine")
    srv.prewarm()
    ctx.phase("prewarm")
    reqs = chat_requests(tr, srv.cfg.vocab_size,
                         [tr["warmup_s"], ctx.seconds, 1.0], ctx.seed)
    d = srv.drive(reqs, tr["warmup_s"], ctx.seconds, tr["drain_s"], ctx)
    st = window_stats(d)
    mem = peak_bytes([dev])
    srv.free()
    ref_cfg = cell.config["reference"]
    sample = sample_for_check(d, ctx.seed, ref_cfg["check_tokens"])
    t_ref = time.time()
    gaps = served_gaps(cell.reference, cell.config["model"], srv.weights(),
                       sample, d, srv.ecfg.kv_capacity,
                       srv.ecfg.max_new_tokens)
    ctx.phase(f"reference {time.time() - t_ref:.3f}s, after window")
    return {
        "setup_s": d["t_w0"] - ctx.t_start,
        "window_s": st["window_s"],
        "e2e": {k: st[k] for k in ("serve_tokens_per_s", "ttft_p95_ms",
                                   "itl_p95_ms")},
        "attempted": st["attempted"], "failed": st["failed"],
        "numbers": {"logit_gap": float(gaps.max()) if gaps.size
                    else float("nan")},
        "memory_peak_bytes": mem,
        "readings": {**st, "checked_requests": len(sample),
                     "checked_tokens": int(gaps.size)},
    }
