"""The program's own spans: their reduction on a hand-built trace, and the
spans a real engine writes into the profiler's trace on the CPU."""
import gc

import jax
import numpy as np
import pytest

from bench import harness
from bench import spans as sp
from bench import trace as tr

S = sp.Span
OLD_READERS = ("device_idle.serve", "decode_ms.serve", "chunk_ms.serve",
               "queue_wait_p95_ms.serve")


def bench_spans():
    return [S(0, 1000, tr.WINDOW_SPAN), S(0, 480, "serve.step"),
            S(500, 480, "serve.step")]


def program_spans():
    """Two engine steps: a chunk and a decode, then a decode alone; a
    garbage collection inside the first decode's harvest."""
    return [
        S(5, 20, "serve.admit", {"admitted": 1, "deferred": 0, "queue": 2}),
        S(30, 200, "serve.chunk"),
        S(30, 20, "serve.chunk.prep"),
        S(50, 30, "serve.chunk.dispatch",
          {"lanes": 4, "lanes_valid": 2, "tokens_valid": 10,
           "tokens_computed": 32, "finished": 1}),
        S(80, 120, "serve.chunk.sync"),
        S(200, 20, "serve.chunk.harvest"),
        S(240, 200, "serve.decode"),
        S(240, 10, "serve.decode.prep"),
        S(250, 20, "serve.decode.dispatch", {"lanes": 4, "committed": 2}),
        S(270, 100, "serve.decode.sync"),
        S(370, 60, "serve.decode.harvest"),
        S(380, 30, "serve.gc", {"generation": 2}),
        S(440, 30, "serve.retire"),
        S(505, 20, "serve.admit", {"admitted": 0, "deferred": 0,
                                   "queue": 1}),
        S(540, 150, "serve.decode"),
        S(540, 10, "serve.decode.prep"),
        S(550, 20, "serve.decode.dispatch", {"lanes": 4, "committed": 3}),
        S(570, 100, "serve.decode.sync"),
        S(670, 20, "serve.decode.harvest"),
        S(700, 30, "serve.retire")]


def serve_trace(with_program: bool, stats: bool = True) -> tr.Trace:
    """One chip; idle gaps [0,60), [210,265), [375,560), [680,1000).
    Program spans follow their enclosing benchmark span, as the profiler
    lists them."""
    dev = tr.DeviceLines(
        ops=[tr.Event(60, 150, "%fusion.1 = f32[8] fusion()"),
             tr.Event(265, 110, "%copy.2 = f32[8] copy()"),
             tr.Event(560, 120, "%copy.3 = f32[8] copy()")],
        modules=[tr.Event(55, 160, "jit_chunk_masked(1)"),
                 tr.Event(260, 120, "jit_decode_masked(2)"),
                 tr.Event(555, 130, "jit_decode_masked(2)")])
    w, step1, step2 = bench_spans()
    prog = program_spans() if with_program else []
    if not stats:
        prog = [tr.Event(s.start_ns, s.dur_ns, s.name) for s in prog]
    spans = [w, step1, *[s for s in prog if s.start_ns < 500], step2,
             *[s for s in prog if s.start_ns >= 500]]
    return tr.Trace({"/device:TPU:0": dev}, spans)


def test_host_ms_sums_host_work_per_step():
    s = tr.summarize(serve_trace(True))
    # admit 40, preps 40, dispatches 70, harvests 100, retire 60: 310 ns
    assert sp.host_ms_per_step(s) == pytest.approx(310 / 2 * 1e-6)


def test_idle_goes_to_the_innermost_span_gc_first():
    s = tr.summarize(serve_trace(True))
    assert s.busy_s == pytest.approx(380e-9)
    idle = {k: round(v * 1e9, 6) for k, v in sp.idle_by_span(s).items()}
    assert idle == {
        "serve.step": 310, "serve.admit": 40, "serve.chunk.prep": 20,
        "serve.chunk.dispatch": 10, "serve.chunk.harvest": 10,
        "serve.chunk": 10, "serve.decode.prep": 20,
        "serve.decode.dispatch": 25, "serve.decode.harvest": 35,
        "serve.gc": 30, "serve.decode": 10, "serve.retire": 60,
        "none": 40}
    assert sum(idle.values()) == pytest.approx(620)
    # host work 220 + garbage collection 30, over a window of 1000
    assert sp.idle_host_share(s) == pytest.approx(25.0)


def test_gc_span_outranks_a_span_that_opens_inside_it():
    spans = [S(0, 100, "serve.decode.harvest"), S(10, 40, "serve.gc"),
             S(20, 10, "serve.retire")]
    split = sp.split_gap((0, 100), spans)
    assert split == {"serve.decode.harvest": 60, "serve.gc": 40}
    assert sp.split_gap((200, 300), spans) == {"none": 100}


def test_dispatch_stats_give_lane_use_and_chunk_fill():
    spans = program_spans()
    assert sp.lane_use(spans) == pytest.approx(100 * 5 / 8)
    assert sp.chunk_fill(spans) == pytest.approx(100 * 10 / 32)


def test_readers_are_silent_without_program_spans():
    s = tr.summarize(serve_trace(False))
    assert sp.host_ms_per_step(s) is None
    assert sp.idle_host_share(s) is None
    assert sp.lane_use(bench_spans()) is None
    assert sp.chunk_fill([]) is None
    cell = harness.load_cell("olmo-serve-chat")
    r = harness.Readings(cell, {"readings": {}}, s, {})
    for name in ("host_ms.serve", "idle_host.serve"):
        assert harness.load_module("metrics", name).read(r) is None


@pytest.mark.parametrize("stats", [True, False])
def test_program_spans_leave_the_old_readings_as_they_were(stats):
    cell = harness.load_cell("olmo-serve-chat")
    job = {"readings": {"queue_wait_p95_ms": 12.5}}
    before = tr.summarize(serve_trace(False))
    after = tr.summarize(serve_trace(True, stats))
    assert after.busy_s == before.busy_s
    assert after.idle_share == before.idle_share
    assert after.breakdown() == before.breakdown()
    assert [g[0] for g in after.breakdown()["idle_gaps"]] == \
        ["serve.step"] * 4
    for name in OLD_READERS:
        read = harness.load_module("metrics", name).read
        assert read(harness.Readings(cell, job, after, {})) == \
            read(harness.Readings(cell, job, before, {}))
    r = harness.Readings(cell, job, after, {})
    assert harness.load_module("metrics", "host_ms.serve").read(r) > 0
    assert harness.load_module("metrics", "idle_host.serve").read(r) > 0


# ---------------------------------------------------------------------------
# A real engine, traced on the CPU
# ---------------------------------------------------------------------------

PARENT = {"serve.chunk.prep": "serve.chunk",
          "serve.chunk.dispatch": "serve.chunk",
          "serve.chunk.sync": "serve.chunk",
          "serve.chunk.harvest": "serve.chunk",
          "serve.decode.prep": "serve.decode",
          "serve.decode.dispatch": "serve.decode",
          "serve.decode.sync": "serve.decode",
          "serve.decode.harvest": "serve.decode",
          "serve.prefill": "serve.admit"}
TOP = ("serve.admit", "serve.chunk", "serve.decode", "serve.retire")


def _inside(c, p):
    return p.start_ns <= c.start_ns and c.end_ns <= p.end_ns


@pytest.mark.parametrize("paged", [False, True])
@pytest.mark.parametrize("chunk", [0, 4])
def test_engine_writes_its_spans_and_counts_its_lanes(paged, chunk,
                                                      tmp_path):
    from repro.configs.base import get_config, reduced
    from repro.models import init_params
    from repro.serve import EngineConfig, Request, ServeEngine
    cfg = reduced(get_config("olmo-1b"), n_layers=1, d_model=32)
    eng = ServeEngine(cfg, EngineConfig(
        max_slots=2, prompt_len=8, max_new_tokens=4, paged=paged,
        page_size=4, prefill_chunk=chunk),
        params=init_params(jax.random.PRNGKey(0), cfg))
    rng = np.random.default_rng(0)
    for i, n in enumerate((3, 8, 5)):
        eng.submit(Request(i, rng.integers(1, cfg.vocab_size, n,
                                           dtype=np.int32)))
    jax.profiler.start_trace(str(tmp_path))
    steps = 0
    while eng.queue or eng.active_count:
        eng.step()
        steps += 1
    gc.collect()
    jax.profiler.stop_trace()
    path = tr.find_xplane(str(tmp_path))
    spans = sp.load_spans(path)
    assert {s.name for s in tr.load(path).spans} == {s.name for s in spans}
    by = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)

    want = {"serve.admit", "serve.decode", "serve.decode.prep",
            "serve.decode.dispatch", "serve.decode.sync",
            "serve.decode.harvest", "serve.retire", "serve.gc"}
    want |= {"serve.chunk", "serve.chunk.prep", "serve.chunk.dispatch",
             "serve.chunk.sync", "serve.chunk.harvest"} if chunk \
        else {"serve.prefill"}
    assert set(by) == want
    assert len(by["serve.admit"]) == len(by["serve.retire"]) == steps
    for child, parent in PARENT.items():
        for c in by.get(child, []):
            assert any(_inside(c, p) for p in by[parent]), child
    top = sorted((s for s in spans if s.name in TOP),
                 key=lambda s: s.start_ns)
    assert all(a.end_ns <= b.start_ns for a, b in zip(top, top[1:]))
    assert any(s.stats["generation"] == 2 for s in by["serve.gc"])

    def total(name, stat):
        return sum(s.stats[stat] for s in by.get(name, []))

    m = eng.metrics
    assert total("serve.admit", "admitted") == 3
    assert total("serve.decode.dispatch", "lanes") == \
        m.decode_lanes_computed == 2 * len(by["serve.decode.dispatch"])
    assert total("serve.decode.dispatch", "committed") == \
        m.decode_lanes_committed
    assert total("serve.chunk.dispatch", "tokens_computed") == \
        m.chunk_tokens_computed
    assert total("serve.chunk.dispatch", "tokens_valid") == \
        m.chunk_tokens_valid == (3 + 8 + 5 if chunk else 0)
    first = total("serve.chunk.dispatch", "finished") if chunk \
        else len(by["serve.prefill"])
    assert m.decode_lanes_committed + first == m.tokens_committed == 3 * 4
    s = m.summary()
    assert s["decode_lane_use"] == pytest.approx(
        sp.lane_use(spans) / 100, abs=1e-4)
    if chunk:
        assert s["chunk_fill"] == pytest.approx(
            sp.chunk_fill(spans) / 100, abs=1e-4)
    else:
        assert s["chunk_fill"] is None and sp.chunk_fill(spans) is None
