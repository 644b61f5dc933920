"""Find a serving cell's knee: its traffic at several fixed rates, one
process, one engine, each rate after a warm-up at that rate.

    python3 bench/tools/sweep.py --workload olmo-serve-chat \
        --rates 1,1.5,2,2.5,3 --seconds 30 --seed 7

Prints one JSON line per rate. The knee is the highest rate at which
nothing is rejected and the queue does not grow across the window: the
queue wait of the window's last third of requests stays near its first
third's, and the queue is as short at the window's end as at its start.
The cell's rate is then fixed in its traffic file. Needs the chip.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default="olmo-serve-chat")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    from bench.harness import load_cell, use_compile_cache
    use_compile_cache()
    import jax
    from bench.jobs.serve import Server, window_stats
    from bench.stats import percentile
    from bench.traffic import chat_requests
    cell = load_cell(args.workload)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("sweep: needs the chip", file=sys.stderr)
        return 3
    srv = Server(cell, dev, args.seed)
    srv.prewarm()
    tr = cell.traffic
    for rate in [float(x) for x in args.rates.split(",")]:
        srv.engine.drain()
        # request ids restart at 0 for every rate: drop the last rate's
        # completions so none is taken for this rate's
        srv.engine.completions.clear()
        reqs = chat_requests(dict(tr, rate_rps=rate), srv.cfg.vocab_size,
                             [tr["warmup_s"], args.seconds, 1.0], args.seed)
        t0 = time.time()
        d = srv.drive(reqs, tr["warmup_s"], args.seconds, tr["drain_s"])
        st = window_stats(d)
        waits = [1e3 * (d["done"][k].t_admit - d["due"][k])
                 for k in d["in_window"] if k in d["done"]]
        third = max(1, len(waits) // 3)
        print(json.dumps({
            "rate_rps": rate, **{k: st[k] for k in (
                "serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms",
                "queue_wait_p95_ms", "attempted", "failed", "rejected")},
            "queue_wait_p50_first_third_ms": percentile(waits[:third], 50),
            "queue_wait_p50_last_third_ms": percentile(waits[-third:], 50),
            "queue_len_end": len(srv.engine.queue),
            "wall_s": time.time() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
