"""Paged KV cache + chunked prefill (serve/paged.py, serve/engine.py).

Three layers of coverage:

* allocator properties (hypothesis): alloc/free roundtrips, all-or-nothing
  exhaustion (rejection, never corruption), no page aliasing across live
  grants, full free-list restoration;
* scatter/gather units: a pool scatter followed by ``gather_pages`` is the
  identity onto the contiguous cache layout, and the in-place row write
  matches a row-by-row NumPy oracle;
* engine integration: ragged-prompt admission on an SSM and an attention
  arch, pool-exhaustion deferral (second backpressure signal), oversize
  rejection, and the paged/chunked engines' bitwise agreement with the
  dense blocking oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:                                   # property tests: hypothesis when
    from hypothesis import given, strategies as st      # available,
    _HYP = True                        # deterministic grid otherwise (the
except ImportError:                    # container may not ship it; no
    _HYP = False                       # installs — gate, don't skip all)


def _cases(*pairs):
    """@given over the strategies, or a parametrized fallback grid."""
    names = [p[0] for p in pairs]
    if _HYP:
        strats = {n: st.integers(lo, hi) for n, lo, hi in pairs}
        return given(**strats)
    rng = np.random.default_rng(0)
    grid = [tuple(int(rng.integers(lo, hi + 1)) for _, lo, hi in pairs)
            for _ in range(8)]
    grid += [tuple(lo for _, lo, _hi in pairs)]       # always the corner
    if len(names) == 1:
        grid = [g[0] for g in grid]
    return pytest.mark.parametrize(",".join(names), grid)

from repro.configs.base import get_config, reduced
from repro.models import init_params
from repro.models.attention import gather_pages
from repro.serve import EngineConfig, Request, ServeEngine
from repro.serve import paged as P


def _cfg(arch="mamba2-780m", d_model=32):
    return reduced(get_config(arch), n_layers=2, d_model=d_model)


def _params(cfg, seed=0):
    return init_params(jax.random.PRNGKey(seed), cfg)


def _ragged_prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, L).astype(np.int32)
            for L in lens]


def _tokens(engine):
    return {c.rid: c.tokens.tolist() for c in engine.completions}


# ---------------------------------------------------------------------------
# PageAllocator properties
# ---------------------------------------------------------------------------


@_cases(("n_pages", 1, 64), ("seed", 0, 10_000))
def test_allocator_roundtrip_and_no_aliasing(n_pages, seed):
    """Random alloc/free interleavings: live grants never share a page,
    the free+used partition always covers exactly [0, n_pages), and
    freeing everything restores the full pool."""
    rng = np.random.default_rng(seed)
    alloc = P.PageAllocator(n_pages)
    grants = []
    for _ in range(50):
        if grants and rng.random() < 0.4:
            alloc.free(grants.pop(rng.integers(len(grants))))
        else:
            got = alloc.alloc(int(rng.integers(1, n_pages + 2)))
            if got is not None:
                grants.append(got)
        live = [p for g in grants for p in g]
        assert len(live) == len(set(live))          # no aliasing
        assert alloc.in_use == len(live)
        assert alloc.free_count + alloc.in_use == n_pages
    for g in grants:
        alloc.free(g)
    assert alloc.free_count == n_pages and alloc.in_use == 0


@_cases(("n_pages", 1, 16))
def test_allocator_exhaustion_is_rejection_not_corruption(n_pages):
    """An oversized request returns None and leaves the pool untouched —
    all-or-nothing, never a partial grant."""
    alloc = P.PageAllocator(n_pages)
    grant = alloc.alloc(n_pages)
    assert grant is not None and len(grant) == n_pages
    before = (alloc.free_count, alloc.in_use)
    assert alloc.alloc(1) is None
    assert (alloc.free_count, alloc.in_use) == before
    alloc.free(grant)
    assert alloc.alloc(n_pages + 1) is None          # bigger than the pool
    assert alloc.free_count == n_pages


def test_allocator_double_free_asserts():
    alloc = P.PageAllocator(4)
    g = alloc.alloc(2)
    alloc.free(g)
    with pytest.raises(AssertionError, match="double free"):
        alloc.free(g)


# ---------------------------------------------------------------------------
# scatter + gather: identity onto the contiguous layout
# ---------------------------------------------------------------------------


def test_scatter_then_gather_is_contiguous_identity():
    """Rows scattered through two lanes' page tables gather back as
    exactly the contiguous [len, KVH, hd] prefix of each lane's cache."""
    page, n_pp, kvh, hd = 4, 3, 2, 5
    pool = jnp.zeros((8, page, kvh, hd), jnp.float32)
    rng = np.random.default_rng(0)
    tables = jnp.asarray([[5, 1, 7], [2, 6, 0]], jnp.int32)
    lens = jnp.asarray([0, 3], jnp.int32)            # lane 1 mid-sequence
    T = 6
    rows = jnp.asarray(rng.normal(size=(2, T, kvh, hd)), jnp.float32)
    n_valid = jnp.asarray([T, 4], jnp.int32)         # lane 1 length-masked
    pool = P.scatter_rows(pool, rows, tables, lens, n_valid,
                          jnp.asarray([True, True]), page)
    for b, (ln, nv) in enumerate([(0, T), (3, 4)]):
        got = gather_pages(pool, tables[b])[0]        # [n_pp*page, kvh, hd]
        np.testing.assert_array_equal(
            np.asarray(got[ln:ln + nv]), np.asarray(rows[b, :nv]))
    # masked lane commits nothing, even with live-looking rows
    before = pool
    pool = P.scatter_rows(pool, rows, tables, lens, n_valid,
                          jnp.asarray([False, False]), page)
    np.testing.assert_array_equal(np.asarray(pool), np.asarray(before))


def _scatter_oracle(pool, rows, tables, lens, n_valid, commit, page):
    """Row-by-row NumPy write: lane b's token t goes to row (lens+t) % page
    of page tables[b, (lens+t) // page], iff commit[b] and t < n_valid[b]."""
    out = np.array(pool)
    for b in range(rows.shape[0]):
        for t in range(rows.shape[-3]):
            if not commit[b] or t >= n_valid[b]:
                continue
            pos = lens[b] + t
            pid, r = tables[b, pos // page], pos % page
            if out.ndim == 5:
                out[:, pid, r] = rows[b, :, t]
            else:
                out[pid, r] = rows[b, t]
    return out


@pytest.mark.parametrize("commit", [(True, True, True), (True, False, True)],
                         ids=["all-commit", "lane1-masked"])
@pytest.mark.parametrize("T", [1, 6], ids=["decode", "chunk"])
@pytest.mark.parametrize("layers", [0, 3], ids=["tail-4d", "blocks-5d"])
def test_scatter_rows_matches_numpy_oracle(layers, T, commit):
    """The in-place row write against a row-by-row NumPy oracle: lanes
    that start mid-page and cross page boundaries, a lane masked by
    `commit`, tokens past `n_valid` (some past the lane's page table).
    Every hit row holds its new row and every other row keeps its bytes."""
    page, n_pp, G, kvh, hd = 4, 3, 10, 2, 5
    lead = (layers,) if layers else ()
    rng = np.random.default_rng(T + layers)
    pool = rng.normal(size=lead[:1] + (G, page, kvh, hd)).astype(np.float32)
    tables = np.asarray([[5, 1, 7], [2, 6, 0], [9, 3, 8]], np.int32)
    if T == 1:
        lens, n_valid = [2, 3, 11], [1, 0, 1]          # lane 1: no token
    else:
        lens, n_valid = [2, 3, 7], [6, 4, 5]           # lane 2 ends at row 11
    lens, n_valid = np.asarray(lens, np.int32), np.asarray(n_valid, np.int32)
    commit = np.asarray(commit)
    rows = rng.normal(size=(3,) + lead + (T, kvh, hd)).astype(np.float32)
    got = P.scatter_rows(jnp.asarray(pool), jnp.asarray(rows),
                         jnp.asarray(tables), jnp.asarray(lens),
                         jnp.asarray(n_valid), jnp.asarray(commit), page)
    want = _scatter_oracle(pool, rows, tables, lens, n_valid, commit, page)
    np.testing.assert_array_equal(np.asarray(got), want)
    n_hit = int(sum(min(T, n) for n, c in zip(n_valid, commit) if c))
    changed = (want != pool).any(axis=(-2, -1))
    if layers:
        changed = changed.any(axis=0)
    assert n_hit > 0 and int(changed.sum()) == n_hit


# ---------------------------------------------------------------------------
# Engine integration
# ---------------------------------------------------------------------------


def _run(arch, lens, seed=0, **kw):
    cfg = _cfg(arch)
    ecfg = EngineConfig(max_slots=2, prompt_len=8, max_new_tokens=8,
                        queue_depth=16, seed=seed, **kw)
    eng = ServeEngine(cfg, ecfg, params=_params(cfg))
    for i, p in enumerate(_ragged_prompts(cfg, lens)):
        assert eng.submit(Request(i, p))
    eng.drain()
    return eng


@pytest.mark.parametrize("arch", ["mamba2-780m", "olmo-1b"])
def test_ragged_admission_chunked_matches_blocking(arch):
    """Ragged prompts (the old engine hard-asserted fixed length) complete
    under every engine mode; greedy chunked output matches the blocking
    oracle, and chunked admission never recompiles (ragged = masking)."""
    lens = [3, 8, 5, 1, 7]
    base = _tokens(_run(arch, lens, paged=False))    # dense blocking oracle
    chunked = _run(arch, lens, prefill_chunk=4, paged=False)
    assert _tokens(chunked) == base
    s = chunked.metrics.summary()
    assert s["completed"] == len(lens)
    assert s["prefill_cache_misses"] == 0
    assert s["decode_cache_misses"] == 0
    paged = _run(arch, lens, prefill_chunk=4, paged=True, page_size=4)
    assert _tokens(paged) == _tokens(chunked)        # bitwise pair


def test_pool_exhaustion_defers_then_completes():
    """A pool with pages for ONE lane at a time: concurrent admissions
    defer at the queue head (counted), nothing is rejected or corrupted,
    and every request completes once pages free up."""
    cfg = _cfg("olmo-1b")
    ecfg = EngineConfig(max_slots=2, prompt_len=8, max_new_tokens=8,
                        queue_depth=16, paged=True, page_size=4, n_pages=4)
    assert ecfg.pages_per_lane == 4                  # = the whole pool
    eng = ServeEngine(cfg, ecfg, params=_params(cfg))
    for i, p in enumerate(_ragged_prompts(cfg, [8, 8, 8])):
        assert eng.submit(Request(i, p))
    eng.drain()
    s = eng.metrics.summary()
    assert s["completed"] == 3 and s["rejected"] == 0
    assert s["pool_deferrals"] > 0
    assert s["dropped_in_flight"] == 0
    assert eng.allocator.in_use == 0                 # all pages returned
    # serialized admissions must still match the unconstrained engine
    free = _run("olmo-1b", [8, 8, 8], paged=True, page_size=4)
    assert _tokens(eng) == _tokens(free)


def test_oversize_prompt_raises():
    cfg = _cfg()
    ecfg = EngineConfig(max_slots=1, prompt_len=8, max_new_tokens=8)
    eng = ServeEngine(cfg, ecfg, params=_params(cfg))
    eng.submit(Request(0, np.zeros(12, np.int32)))   # 12 + 8 > 16
    with pytest.raises(ValueError, match="kv_capacity"):
        eng.step()


def test_paged_pool_smaller_than_dense_bank_at_half_occupancy():
    """The t15 memory claim at unit scale: a pool sized for 50% slot
    occupancy costs less device memory than the dense full-attention
    bank (metrics expose both sides)."""
    cfg = _cfg("olmo-1b")
    ecfg = EngineConfig(max_slots=4, prompt_len=8, max_new_tokens=8,
                        paged=True, page_size=4,
                        n_pages=2 * (16 // 4))       # 2 of 4 lanes' worth
    eng = ServeEngine(cfg, ecfg, params=_params(cfg))
    s = eng.metrics.summary()
    assert 0 < s["kv_bytes"] < s["kv_dense_bytes"]
