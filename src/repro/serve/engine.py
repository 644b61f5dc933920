"""Continuous-batching serving engine (DESIGN.md §Serving).

Slot-based scheduling over the repo's prefill/decode fns: sequences join
and retire MID-BATCH by flipping a lane mask — the same masking
discipline the training engine uses for churn (core/swarm.py): every
lane computes every step, only masked lanes COMMIT, so all shapes are
static and each serving fn compiles exactly once.

KV memory comes in two layouts:

* dense (default): a fixed bank of `max_slots` per-sequence lanes, every
  cache leaf with a leading slot axis (decode is vmapped over it);
* paged (``EngineConfig.paged`` / REPRO_SERVE_PAGED): full-attention
  layers share global page pools + per-lane page tables (serve/paged.py);
  pages alloc on admit, free on retire, and an admission that cannot get
  pages DEFERS — pool pressure is a second backpressure signal next to
  the bounded queue. Decode gathers a lane's pages back to the contiguous
  layout, so the paged token stream is BITWISE the dense engine's (the
  dense engine is the retained oracle, tests/test_serve.py).

Prefill comes in two schedules:

* blocking (default): admission runs a batch-1 prefill to completion and
  installs the cache — simple, but every arrival stalls all live decode
  lanes for the full prompt (head-of-line blocking). Ragged prompts
  dispatch at their own length (one compile per distinct length).
* chunked (``prefill_chunk`` > 0 / REPRO_PREFILL_CHUNK): prompts prefill
  in fixed-shape [slots, T] token chunks, one chunk dispatch interleaved
  with the decode dispatch per engine step, masked commits — ragged
  prompts are length-masked chunks and NOTHING recompiles. Decode lanes
  keep committing tokens while prompts prefill, which is what flattens
  in-flight p99 under bursts (benchmarks t15).

Hot swap (serve/swap.py) composes with the batch through generations: a
lane is pinned to the param generation it was ADMITTED under and finishes
on it; at most two generations are ever live, each serving fn runs one
masked dispatch per live generation — same shapes, so a swap is a
jit-cache HIT (the engine counts cache misses; t15 asserts zero).

Admission control: a bounded FIFO queue (`queue_depth`); `submit` on a
full queue REJECTS (backpressure to the client) and counts it — the
server degrades by shedding load, never by growing latency without bound.
"""
from __future__ import annotations

import os
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import forward, init_cache
from repro.models.transformer import logits_head
from repro.profiling import install_gc_spans, span
from repro.serve import paged as P
from repro.serve.metrics import ServeMetrics
from repro.serve.swap import HotSwap


def grow_cache(full, cache):
    """Copy a (smaller) prefill cache into a full-capacity cache bank.

    Every leaf must either match shapes exactly or grow into a same-rank
    leaf that is at least as large on every axis; anything else raises
    with the offending leaf path — a shape mismatch silently keeping the
    EMPTY destination (the historical fallback) would serve garbage KV
    state. Used by the one-shot oracle path (launch/serve.py); the
    engine itself installs prefill caches with a single
    dynamic_update_slice per leaf (no grown intermediate copy).
    """
    def grow(path, dst, src):
        name = jax.tree_util.keystr(path)
        if dst.ndim != src.ndim:
            raise ValueError(
                f"cache leaf {name}: rank mismatch {src.shape} -> "
                f"{dst.shape}; prefill and serving caches must share "
                "structure")
        if dst.shape == src.shape:
            return src
        if any(d < s for d, s in zip(dst.shape, src.shape)):
            raise ValueError(
                f"cache leaf {name}: cannot grow {src.shape} into smaller "
                f"{dst.shape}")
        return dst.at[tuple(slice(0, s) for s in src.shape)].set(src)
    return jax.tree_util.tree_map_with_path(grow, full, cache)


def _env_flag(name: str) -> bool:
    return os.environ.get(name, "") not in ("", "0")


@dataclass(frozen=True)
class EngineConfig:
    max_slots: int = 4           # concurrent sequences (KV-cache lanes)
    prompt_len: int = 32         # default/maximum admission prompt length
    max_new_tokens: int = 16     # default per-request generation budget
    cache_size: int = 0         # 0 = prompt_len + max_new_tokens
    queue_depth: int = 16        # bounded admission queue (backpressure)
    temperature: float = 0.0     # 0 = greedy (deterministic serving)
    seed: int = 0
    # paged KV (serve/paged.py). page_size is rows per page; n_pages sizes
    # the global pool (0 = enough for every lane at full capacity — no
    # memory saving, but no admission can ever starve). Architectures
    # with no full-attention layer (pure SSM) run dense: paging is a
    # documented no-op there.
    paged: bool = field(
        default_factory=lambda: _env_flag("REPRO_SERVE_PAGED"))
    page_size: int = field(default_factory=lambda: int(
        os.environ.get("REPRO_SERVE_PAGE_SIZE", "8")))
    n_pages: int = 0
    # chunked prefill: tokens per prefill chunk; 0 = blocking admission
    prefill_chunk: int = field(default_factory=lambda: int(
        os.environ.get("REPRO_PREFILL_CHUNK", "0")))

    @property
    def kv_capacity(self) -> int:
        base = self.cache_size or (self.prompt_len + self.max_new_tokens)
        if self.paged:
            # page-aligned so a page table covers exactly the capacity;
            # bitwise-vs-dense tests pick page_size dividing the capacity
            # (same softmax reduction shape), see DESIGN.md §Serving
            base = -(-base // self.page_size) * self.page_size
        return base

    @property
    def pages_per_lane(self) -> int:
        return self.kv_capacity // self.page_size

    @property
    def pool_pages(self) -> int:
        return self.n_pages or (self.max_slots * self.pages_per_lane)


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                   # [L] int32, L <= prompt_len
    max_new_tokens: int = 0              # 0 = engine default
    t_submit: float = 0.0


@dataclass
class Completion:
    rid: int
    tokens: np.ndarray                   # [n_generated] int32
    gen: int                             # param generation served under
    t_submit: float
    t_admit: float
    t_first_token: float
    t_done: float


@dataclass
class _Lane:
    rid: int = -1
    gen: int = -1
    active: bool = False
    prefilling: bool = False
    pos: int = 0                         # prompt tokens consumed (chunked)
    prompt: Optional[np.ndarray] = None
    budget: int = 0
    remaining: int = 0
    pages: Optional[List[int]] = None
    tokens: List[int] = field(default_factory=list)
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first: float = 0.0
    t_last: float = 0.0                  # last token commit (gap metric)


class ServeEngine:
    """Continuous-batching engine over one model config.

    `source` is any object with ``poll() -> Optional[ModelUpdate]``
    (serve/source.py); `params` seeds generation 1 directly when no source
    is used (the one-shot/oracle mode). At least one of the two must
    provide a model before the first admission.
    """

    def __init__(self, cfg, ecfg: EngineConfig, *, params=None, source=None):
        if cfg.frontend is not None:
            raise ValueError(
                f"{cfg.name}: the continuous-batching engine serves "
                "token-only architectures; multimodal prefix serving runs "
                "through the one-shot path (launch/serve.py)")
        self.cfg = cfg
        self.ecfg = ecfg
        self.source = source
        self.swap = HotSwap()
        self.metrics = ServeMetrics()
        self.queue: Deque[Request] = deque()
        self.lanes = [_Lane() for _ in range(ecfg.max_slots)]
        self.live: Dict[int, Any] = {}       # gen -> params (<= 2 entries)
        self.adopted_gen = -1
        self.completions: List[Completion] = []
        self._key = jax.random.PRNGKey(ecfg.seed)
        # paged is a no-op without full-attention layers (pure-SSM archs)
        self._paged = ecfg.paged and bool(P.attn_layer_entries(cfg))
        self.allocator = P.PageAllocator(ecfg.pool_pages) \
            if self._paged else None
        dtype = jnp.dtype(cfg.dtype)
        self._pools = P.build_pools(cfg, ecfg.pool_pages, ecfg.page_size,
                                    dtype) if self._paged else None
        self._build_fns()
        self._caches = self._init_cache_bank()
        self._tokens = jnp.zeros((ecfg.max_slots, 1), jnp.int32)
        self.metrics.kv_pool_pages = ecfg.pool_pages if self._paged else 0
        self.metrics.kv_bytes = P.tree_num_bytes(self._pools) \
            if self._paged else P.dense_attn_bank_bytes(
                cfg, ecfg.max_slots, ecfg.kv_capacity, dtype)
        self.metrics.kv_dense_bytes = P.dense_attn_bank_bytes(
            cfg, ecfg.max_slots, ecfg.kv_capacity, dtype)
        if params is not None:
            self.swap.publish(params, t_landed=time.time(), tag="init")
        install_gc_spans()

    # -- compiled serving fns (each compiles exactly once) -----------------

    def _build_fns(self):
        cfg, ecfg = self.cfg, self.ecfg
        temp, page = ecfg.temperature, ecfg.page_size

        def sample(logits_v, key):           # [vocab] -> scalar int32
            if temp <= 0:
                return jnp.argmax(logits_v, axis=-1).astype(jnp.int32)
            return jax.random.categorical(
                key, logits_v / temp).astype(jnp.int32)

        def prefill(params, tokens, key):
            hidden, cache, _ = forward(cfg, params, tokens, mode="prefill")
            logits = logits_head(cfg, params, hidden[:, -1:])   # [1,1,V]
            return sample(logits[0, -1], key), cache

        def install(caches, tokens, cache1, tok, i):
            """Install a batch-1 prefill cache (+ its first token) into
            lane i (TRACED: every lane index hits one compilation) — one
            dynamic_update_slice per leaf, no grown intermediate: the
            stale bank tail beyond the prompt is masked at attention
            time, never read."""
            caches = dict(caches)
            pages = caches.pop("pages", None)

            def put(bank, c):
                c = c.astype(bank.dtype)[None]   # scalar "len" -> [1]
                start = (i,) + (0,) * (bank.ndim - 1)
                return jax.lax.dynamic_update_slice(bank, c, start)
            out = jax.tree.map(put, caches, cache1)
            if pages is not None:
                out["pages"] = pages
            return out, jax.lax.dynamic_update_index_in_dim(
                tokens, tok[None], i, 0)

        def sel_commit(commit):
            def sel(new, old):
                m = commit.reshape((-1,) + (1,) * (new.ndim - 1))
                return jnp.where(m, new, old)
            return sel

        def decode_masked(params, caches, pools, tokens, commit, key):
            """One decode step over ALL lanes; only `commit` lanes commit
            their cache/token updates (masking discipline = churn)."""
            def one(cache, tok):
                hidden, c2, _ = forward(cfg, params, tok[None, :],
                                        mode="decode", cache=cache,
                                        pools=pools)
                return logits_head(cfg, params, hidden)[0, -1], c2
            logits, new_caches = jax.vmap(one)(caches, tokens)  # [slots,V]
            keys = jax.random.split(key, ecfg.max_slots)
            toks = jax.vmap(sample)(logits, keys)               # [slots]
            new_caches, rows = P.split_new_rows(new_caches)
            caches_out = jax.tree.map(sel_commit(commit), new_caches,
                                      caches)
            if rows is not None:
                pools = P.scatter_tree(
                    pools, rows, caches["pages"], caches["len"],
                    jnp.ones((ecfg.max_slots,), jnp.int32), commit, page)
            toks_out = jnp.where(commit, toks, tokens[:, 0])[:, None]
            return toks_out, caches_out, pools

        def chunk_masked(params, caches, pools, tokens, chunks, n_valid,
                         commit, finish, key):
            """One [slots, T] prefill-chunk step; `commit` lanes advance
            their caches by n_valid tokens, `finish` lanes (final chunk)
            also commit the prompt's next-token sample as their first
            generated token."""
            def one(cache, toks, nv):
                hidden, c2, _ = forward(cfg, params, toks[None, :],
                                        mode="chunk", cache=cache,
                                        n_valid=nv, pools=pools)
                last = jax.lax.dynamic_slice_in_dim(
                    hidden, jnp.maximum(nv - 1, 0), 1, axis=1)
                return logits_head(cfg, params, last)[0, -1], c2
            logits, new_caches = jax.vmap(one)(caches, chunks, n_valid)
            keys = jax.random.split(key, ecfg.max_slots)
            toks = jax.vmap(sample)(logits, keys)
            new_caches, rows = P.split_new_rows(new_caches)
            caches_out = jax.tree.map(sel_commit(commit), new_caches,
                                      caches)
            if rows is not None:
                pools = P.scatter_tree(pools, rows, caches["pages"],
                                       caches["len"], n_valid, commit, page)
            toks_out = jnp.where(finish, toks, tokens[:, 0])[:, None]
            return toks_out, caches_out, pools

        def reset_lane(caches, i):
            """Zero lane i's recurrent state before chunked prefill: len
            and mamba conv/ssm must restart from scratch (chunk mode
            RESUMES them); attention rows are overwritten/masked and swa
            ring garbage is invalidated via min_kpos, so KV stays."""
            def z(path, leaf):
                names = {getattr(p, "key", None) for p in path}
                if names & {"conv", "ssm", "len"}:
                    return leaf.at[i].set(jnp.zeros_like(leaf[0]))
                return leaf
            return jax.tree_util.tree_map_with_path(z, caches)

        def install_pool(pools, rows, table_row, length):
            """Blocking-admit install of a prefilled prompt's attention
            rows into the page pools (one lane; per-prompt-length
            compile, like the blocking prefill itself)."""
            return P.scatter_tree(
                pools, rows, table_row[None], jnp.zeros((1,), jnp.int32),
                length[None], jnp.ones((1,), bool), page)

        self._prefill = jax.jit(prefill)
        self._install = jax.jit(install)
        # the lane cache bank and the page pools are donated: each call
        # replaces them (the engine rebinds both from its outputs), so the
        # new KV rows are written into the pools where they lie
        self._decode = jax.jit(decode_masked, donate_argnums=(1, 2))
        self._chunk_fn = jax.jit(chunk_masked, donate_argnums=(1, 2))
        self._reset = jax.jit(reset_lane)
        self._install_pool = jax.jit(install_pool, donate_argnums=0)

    def _init_cache_bank(self):
        one = init_cache(self.cfg, 1, self.ecfg.kv_capacity)
        if self._paged:
            one, _ = P.strip_attn_kv(self.cfg, one)
        bank = jax.tree.map(
            lambda x: jnp.stack([x] * self.ecfg.max_slots), one)
        if self._paged:
            bank["pages"] = jnp.full(
                (self.ecfg.max_slots, self.ecfg.pages_per_lane), -1,
                jnp.int32)
        return bank

    # -- model management --------------------------------------------------

    def poll_source(self):
        """Pull at most one fresh model from the source into the swap."""
        if self.source is None:
            return
        upd = self.source.poll()
        if upd is not None:
            self.swap.publish(upd.params, t_landed=upd.t_landed,
                              tag=upd.tag)

    def _gens_in_use(self) -> set:
        return {ln.gen for ln in self.lanes if ln.active}

    def _try_adopt(self):
        """Adopt the newest published generation for NEW admissions.

        Double-buffer invariant: at most two generations live at once —
        adoption DEFERS while two distinct generations still hold active
        lanes (the draining one finishes first; sequences are finite, so
        this always unblocks)."""
        latest = self.swap.latest()
        if latest is None:
            return
        gen, params = latest
        if gen == self.adopted_gen:
            return
        in_use = self._gens_in_use()
        if len(in_use - {gen}) >= 2:
            return                         # two gens draining: defer
        assert gen > self.adopted_gen, "generation tags must be monotone"
        self.adopted_gen = gen
        self.live[gen] = params
        self.metrics.record_adoption(gen, self.swap.landed_at(gen))
        self._gc_live()

    def _gc_live(self):
        keep = self._gens_in_use() | {self.adopted_gen}
        for g in [g for g in self.live if g not in keep]:
            del self.live[g]

    # -- admission ---------------------------------------------------------

    def submit(self, req: Request) -> bool:
        """Bounded-queue admission: False = rejected (backpressure)."""
        if len(self.queue) >= self.ecfg.queue_depth:
            self.metrics.rejected += 1
            self.metrics.record_queue(len(self.queue))
            return False
        self.metrics.submitted += 1
        if not req.t_submit:
            req.t_submit = time.time()
        self.queue.append(req)
        self.metrics.record_queue(len(self.queue))
        return True

    def _free_lanes(self) -> List[int]:
        return [i for i, ln in enumerate(self.lanes) if not ln.active]

    def _admit(self, now: float):
        """Move queued requests into free lanes under the adopted
        generation. Blocking mode prefills the prompt here; chunked mode
        only claims the lane (and, paged, its pages) — prefill happens in
        the step's chunk dispatches. Paged: an admission that cannot get
        its pages DEFERS at the queue head (second backpressure signal)."""
        if self.adopted_gen < 0:
            return
        params = self.live[self.adopted_gen]
        for i in self._free_lanes():
            if not self.queue:
                break
            req = self.queue[0]
            prompt = np.asarray(req.prompt, np.int32).reshape(-1)
            L = prompt.shape[0]
            budget = req.max_new_tokens or self.ecfg.max_new_tokens
            if L + budget > self.ecfg.kv_capacity:
                raise ValueError(
                    f"request {req.rid}: prompt {L} + budget {budget} "
                    f"exceeds kv_capacity {self.ecfg.kv_capacity}")
            pages = None
            if self._paged:
                need = -(-(L + budget) // self.ecfg.page_size)
                pages = self.allocator.alloc(need)
                if pages is None:
                    self.metrics.pool_deferrals += 1
                    break                # pool exhausted: stay queued
                self.metrics.record_pool(self.allocator.in_use)
                table = np.full((self.ecfg.pages_per_lane,), -1, np.int32)
                table[:need] = pages
                self._caches["pages"] = \
                    self._caches["pages"].at[i].set(jnp.asarray(table))
            self.queue.popleft()
            self.metrics.record_queue_wait(now - req.t_submit)
            ln = self.lanes[i]
            ln.rid, ln.gen, ln.active = req.rid, self.adopted_gen, True
            ln.prompt, ln.budget, ln.pages = prompt, budget, pages
            ln.t_submit, ln.t_admit = req.t_submit, now
            if self.ecfg.prefill_chunk > 0:
                ln.prefilling, ln.pos, ln.tokens = True, 0, []
                self._caches = self._reset(self._caches, i)
            else:
                with span("serve.prefill"):
                    self._admit_blocking(i, ln, params)

    def _admit_blocking(self, i: int, ln: _Lane, params):
        """Legacy blocking admission: batch-1 prefill at the prompt's own
        length (one compile per distinct length), single-copy install."""
        self._key, sub = jax.random.split(self._key)
        tok1, c1 = self._prefill(params, jnp.asarray(ln.prompt)[None, :],
                                 sub)
        if self._paged:
            c1, rows = P.strip_attn_kv(self.cfg, c1)
            rows = {g: {k: {kv: (jnp.moveaxis(a, 1, 0) if g == "blocks"
                                 else a)
                            for kv, a in lay.items()}
                        for k, lay in grp.items()}
                    for g, grp in rows.items()}
            if rows:
                self._pools = self._install_pool(
                    self._pools, rows, self._caches["pages"][i],
                    jnp.asarray(ln.prompt.shape[0], jnp.int32))
        self._caches, self._tokens = self._install(
            self._caches, self._tokens, c1, tok1, i)
        t1 = time.time()
        ln.tokens = [int(tok1)]
        ln.remaining = ln.budget - 1
        ln.t_first = ln.t_last = t1
        self.metrics.record_ttft(t1 - ln.t_submit)
        self.metrics.tokens_committed += 1
        self.metrics.record_first_token(ln.gen, t1)
        if ln.remaining <= 0:
            self._retire(i)

    # -- decode / harvest --------------------------------------------------

    def _retire(self, i: int):
        ln = self.lanes[i]
        if ln.pages:
            self.allocator.free(ln.pages)
        self.completions.append(Completion(
            ln.rid, np.asarray(ln.tokens, np.int32), ln.gen,
            ln.t_submit, ln.t_admit, ln.t_first, time.time()))
        self.metrics.completed += 1
        self.lanes[i] = _Lane()

    def _step_chunks(self, g: int, params) -> int:
        """One [slots, T] prefill-chunk dispatch for generation g's
        prefilling lanes (fixed shapes: compiles once). Returns tokens
        committed (first tokens of lanes that finished their prompt)."""
        slots, T = self.ecfg.max_slots, self.ecfg.prefill_chunk
        pre = np.array([ln.active and ln.gen == g and ln.prefilling
                        for ln in self.lanes])
        if not pre.any():
            return 0
        with span("serve.chunk"):
            with span("serve.chunk.prep"):
                chunks = np.zeros((slots, T), np.int32)
                nv = np.zeros((slots,), np.int32)
                fin = np.zeros((slots,), bool)
                for i, ln in enumerate(self.lanes):
                    if pre[i]:
                        L = ln.prompt.shape[0]
                        n = min(T, L - ln.pos)
                        chunks[i, :n] = ln.prompt[ln.pos:ln.pos + n]
                        nv[i], fin[i] = n, ln.pos + n >= L
                valid = int(nv.sum())
                self.metrics.record_chunk(slots * T, valid)
            with span("serve.chunk.dispatch", lanes=slots,
                      lanes_valid=int(pre.sum()), tokens_valid=valid,
                      tokens_computed=slots * T, finished=int(fin.sum())):
                self._key, sub = jax.random.split(self._key)
                toks, self._caches, self._pools = self._chunk_fn(
                    params, self._caches, self._pools, self._tokens,
                    jnp.asarray(chunks), jnp.asarray(nv), jnp.asarray(pre),
                    jnp.asarray(fin), sub)
                self._tokens = toks
            with span("serve.chunk.sync"):
                toks_np = np.asarray(toks) if fin.any() else None
            with span("serve.chunk.harvest"):
                committed = 0
                t_now = time.time()
                for i, ln in enumerate(self.lanes):
                    if not pre[i]:
                        continue
                    ln.pos += int(nv[i])
                    if fin[i]:
                        ln.prefilling = False
                        ln.tokens = [int(toks_np[i, 0])]
                        ln.remaining = ln.budget - 1
                        ln.t_first = ln.t_last = t_now
                        self.metrics.record_ttft(t_now - ln.t_submit)
                        self.metrics.tokens_committed += 1
                        self.metrics.record_first_token(ln.gen, t_now)
                        committed += 1
                        if ln.remaining <= 0:
                            self._retire(i)
        return committed

    def _step_decode(self, g: int, params) -> int:
        """One decode dispatch over every lane; generation g's decoding
        lanes commit. Returns tokens committed."""
        commit = np.array([ln.active and ln.gen == g and
                           not ln.prefilling and ln.remaining > 0
                           for ln in self.lanes])
        if not commit.any():
            return 0
        with span("serve.decode"):
            with span("serve.decode.prep"):
                n = int(commit.sum())
                self.metrics.record_decode(self.ecfg.max_slots, n)
                self._key, sub = jax.random.split(self._key)
            with span("serve.decode.dispatch", lanes=self.ecfg.max_slots,
                      committed=n):
                toks, self._caches, self._pools = self._decode(
                    params, self._caches, self._pools, self._tokens,
                    jnp.asarray(commit), sub)
                self._tokens = toks
            with span("serve.decode.sync"):
                toks_np = np.asarray(toks)
            with span("serve.decode.harvest"):
                t_now = time.time()
                for i, ln in enumerate(self.lanes):
                    if commit[i]:
                        ln.tokens.append(int(toks_np[i, 0]))
                        ln.remaining -= 1
                        self.metrics.record_token_gap(t_now - ln.t_last)
                        ln.t_last = t_now
                self.metrics.tokens_committed += n
        return n

    def step(self) -> int:
        """One engine iteration: poll -> adopt -> admit -> per live
        generation one chunk dispatch (chunked prefill) + one decode
        dispatch -> harvest -> retire. Returns # tokens committed.

        Each phase is a `serve.*` host span (repro.profiling); the
        dispatch spans carry the lanes computed and committed."""
        now = time.time()
        if self.metrics.t_start is None:
            self.metrics.t_start = now
        with span("serve.admit") as sp:
            queued, deferred = len(self.queue), self.metrics.pool_deferrals
            self.poll_source()
            self._try_adopt()
            self._admit(now)
            sp.set_metadata(admitted=queued - len(self.queue),
                            deferred=self.metrics.pool_deferrals - deferred,
                            queue=len(self.queue))
        committed = 0
        # one masked dispatch per live generation (usually one; two while
        # a swap drains) — identical shapes, so each is a jit-cache hit
        for g in sorted(self._gens_in_use()):
            params = self.live[g]
            if self.ecfg.prefill_chunk > 0:
                committed += self._step_chunks(g, params)
            committed += self._step_decode(g, params)
        with span("serve.retire"):
            for i, ln in enumerate(self.lanes):
                if ln.active and not ln.prefilling and ln.remaining <= 0:
                    self._retire(i)
            self._gc_live()
            self.metrics.t_end = time.time()
            self.metrics.decode_cache_misses = max(
                0, self._decode._cache_size() - 1)
            if self.ecfg.prefill_chunk > 0:
                self.metrics.prefill_cache_misses = max(
                    0, self._chunk_fn._cache_size() - 1)
        return committed

    def drain(self, max_steps: int = 10_000):
        """Run until queue + lanes are empty (no new arrivals)."""
        for _ in range(max_steps):
            if not self.queue and not any(ln.active for ln in self.lanes):
                return
            self.step()
        raise RuntimeError("drain did not converge")

    @property
    def active_count(self) -> int:
        return sum(ln.active for ln in self.lanes)


def serve_openloop(engine: ServeEngine, arrivals, *, settle_steps: int = 0):
    """Drive the engine under a synthetic OPEN-LOOP arrival process:
    `arrivals` is a list of (t_offset_s, Request) relative to loop start.
    Arrivals are injected by wall clock regardless of engine progress (the
    open-loop property — load does not slow down when the server does);
    returns the engine's completions once all work drains."""
    t0 = time.time()
    pending = sorted(arrivals, key=lambda a: a[0])
    i = 0
    while i < len(pending) or engine.queue or engine.active_count:
        now = time.time() - t0
        while i < len(pending) and pending[i][0] <= now:
            engine.submit(pending[i][1])
            i += 1
        if i < len(pending) and not engine.queue and \
                not engine.active_count:
            time.sleep(min(0.001, max(0.0, pending[i][0] - now)))
            continue
        engine.step()
    for _ in range(settle_steps):
        engine.step()
    return engine.completions
