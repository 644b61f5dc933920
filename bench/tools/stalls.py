"""Where a serving cell's device idles, and whether its engine stalls: one
run of the cell as `bench/run.py` makes it (weights, engine, prewarm,
warm-up, window), without the reference check, and a report on the window.

    python3 bench/tools/stalls.py --workload olmo-serve-chat --seed 7 \
        --seconds 51 --trace 1

Prints one JSON line, traced or not: the end-to-end numbers and the p95
queue wait (so that tracing's own cost can be read at one seed), the
longest engine steps and gaps between tokens, the garbage collections and
the JAX compile and cache events inside the window. With `--trace 1` also
every device idle gap over `--min-gap-ms` with the host spans open in it
(bench/spans.py) and the trace's other events that overlap it (the
runtime's threads, the device's other lines); the idle seconds under each
innermost span; each span's longest and summed time; and the program's
readings: host ms per step, idle share under host work, decode lane use
and chunk fill. Needs the chip.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


class Watch:
    """Engine steps, garbage collections and JAX events, each kept only
    while the window is open."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.steps, self.gcs, self.events = [], [], {}
        self._gc_t0 = None

    def on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None and self.ctx.in_window:
            self.gcs.append((info["generation"],
                             time.perf_counter() - self._gc_t0))

    def on_event(self, event, *a, **kw):
        if self.ctx.in_window:
            self.events[event] = self.events.get(event, 0) + 1

    def timed(self, step):
        def run():
            t0 = time.perf_counter()
            n = step()
            if self.ctx.in_window:
                self.steps.append(time.perf_counter() - t0)
            return n
        return run


def runtime_events(path: str, gaps, top: int = 8) -> list:
    """For each gap, the other events of the trace that overlap it (the
    runtime's host threads, the device's other lines), longest overlap
    first: what the process did while the device idled."""
    from jax.profiler import ProfileData
    from bench.trace import SPAN_PREFIXES
    found = [{} for _ in gaps]
    lo = min((a for a, _ in gaps), default=0.0)
    hi = max((b for _, b in gaps), default=0.0)
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                s, t = e.start_ns, e.start_ns + e.duration_ns
                if t <= lo or s >= hi or e.name.startswith(SPAN_PREFIXES):
                    continue
                for i, (a, b) in enumerate(gaps):
                    ov = min(b, t) - max(a, s)
                    if ov > 0:
                        k = f"{plane.name}|{line.name}|{e.name[:80]}"
                        found[i][k] = found[i].get(k, 0.0) + ov * 1e-6
    return [dict(sorted(f.items(), key=lambda kv: -kv[1])[:top])
            for f in found]


def trace_report(trace_dir: str, min_gap_ns: float) -> dict:
    from bench import spans as sp
    from bench import trace as tr
    path = tr.find_xplane(trace_dir)
    s = tr.summarize(tr.load(path))
    stats = tr.in_window(sp.load_spans(path), s.lo, s.hi)
    per_name = {}
    for e in tr.in_window(s.trace.spans, s.lo, s.hi):
        n, tot, top = per_name.get(e.name, (0, 0.0, 0.0))
        per_name[e.name] = (n + 1, tot + e.dur_ns * 1e-6,
                            max(top, e.dur_ns * 1e-6))
    long = sorted(((g, split) for g, split in sp.attributed_gaps(s)
                   if g[1] - g[0] > min_gap_ns), key=lambda gs: gs[0])
    long_gaps = [
        {"at_s": (g[0] - s.lo) * 1e-9, "ms": (g[1] - g[0]) * 1e-6,
         "label": tr.label_gap(g, s.trace.spans),
         "innermost": max(split, key=split.get),
         "split_ms": {k: v * 1e-6 for k, v in sorted(
             split.items(), key=lambda kv: -kv[1])},
         "overlapping_ms": near}
        for (g, split), near in zip(
            long, runtime_events(path, [g for g, _ in long]))]
    return {
        "window_s": s.window_s, "busy_s": s.busy_s,
        "idle_s": s.window_s - s.busy_s,
        "idle_by_span_s": dict(sorted(sp.idle_by_span(s).items(),
                                      key=lambda kv: -kv[1])),
        "long_gaps": long_gaps,
        "spans": {k: {"n": n, "total_ms": t, "max_ms": m}
                  for k, (n, t, m) in sorted(per_name.items())},
        "host_ms.serve": sp.host_ms_per_step(s),
        "idle_host.serve": sp.idle_host_share(s),
        "lane_use.serve": sp.lane_use(stats),
        "chunk_fill.serve": sp.chunk_fill(stats)}


def main(argv=None) -> int:
    t_start = time.time()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default="olmo-serve-chat")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--min-gap-ms", type=float, default=50.0)
    ap.add_argument("--python-tracer", type=int, choices=(0, 1), default=1,
                    help="0: trace without the profiler's Python function "
                    "tracer, which bench/run.py leaves on")
    args = ap.parse_args(argv)
    from bench.harness import use_compile_cache
    use_compile_cache()
    import jax
    if not args.python_tracer:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace = functools.partial(
            jax.profiler.start_trace, profiler_options=opts)
    from bench.harness import Context, _plain, load_cell
    from bench.jobs.serve import Server, window_stats
    from bench.traffic import chat_requests
    cell = load_cell(args.workload)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("stalls: needs the chip", file=sys.stderr)
        return 3
    trace_dir = str(ROOT / ".bench_trace" / f"stalls-{args.seed}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = Context(cell, args.seed, args.seconds, bool(args.trace), [dev],
                  t_start, trace_dir=trace_dir)
    watch = Watch(ctx)
    gc.callbacks.append(watch.on_gc)
    jax.monitoring.register_event_listener(watch.on_event)
    jax.monitoring.register_event_duration_secs_listener(watch.on_event)
    srv = Server(cell, dev, args.seed)
    srv.prewarm()
    srv.engine.step = watch.timed(srv.engine.step)
    tr = cell.traffic
    reqs = chat_requests(tr, srv.cfg.vocab_size,
                         [tr["warmup_s"], args.seconds, 1.0], args.seed)
    d = srv.drive(reqs, tr["warmup_s"], args.seconds, tr["drain_s"], ctx)
    st = window_stats(d)
    out = {"workload": args.workload, "seed": args.seed,
           "trace": args.trace, "python_tracer": args.python_tracer,
           "setup_s": d["t_w0"] - t_start,
           **{k: st[k] for k in ("serve_tokens_per_s", "ttft_p95_ms",
                                 "itl_p95_ms", "queue_wait_p95_ms",
                                 "attempted", "failed")},
           "steps": len(watch.steps),
           "step_ms_top": sorted((1e3 * x for x in watch.steps),
                                 reverse=True)[:10],
           "token_gap_ms_top": sorted((1e3 * x for x in d["gaps_s"]),
                                      reverse=True)[:10],
           "gc": {f"gen{g}": {"n": sum(1 for h, _ in watch.gcs if h == g),
                              "max_ms": 1e3 * max(
                                  (t for h, t in watch.gcs if h == g),
                                  default=0.0)} for g in (0, 1, 2)},
           "jax_events_in_window": watch.events}
    if args.trace:
        out.update(trace_report(trace_dir, 1e6 * args.min_gap_ms))
        shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(_plain(out)), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
