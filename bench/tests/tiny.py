"""Tiny cells for CPU tests: the real configurations and traffic with
their sizes cut, one chip each, written into a scratch root that the
harness reads by name (the real metric lists of BENCHMARK.json)."""
from __future__ import annotations

import json
from pathlib import Path

from bench.harness import BENCH

TRAIN, SERVE = "wmt-swarm-q8-4chip", "olmo-serve-chat"
TINY_MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
              "d_ff": 128, "vocab_size": 256}


def _load(*parts):
    return json.loads((BENCH.joinpath(*parts)).read_text())


def write_root(root: Path, limits: dict) -> Path:
    """A checkout-like root holding the two tiny cells; `limits` maps each
    cell to the limits of its numbers."""
    root = Path(root)
    bench = _load("..", "BENCHMARK.json")
    bench["workloads"] = [
        {"name": TRAIN, "config": "transformer-wmt",
         "traffic": "swarm-32x512", "chips": 1, "why": "tiny"},
        {"name": SERVE, "config": "olmo-1b", "traffic": "chat-poisson",
         "chips": 1, "why": "tiny"}]
    if not any(m["name"] == "train_tokens_per_s" for m in bench["end_to_end"]):
        bench["end_to_end"].append(
            {"name": "train_tokens_per_s", "unit": "tokens/s",
             "better": "higher", "bound": 0.05, "source": "host_clock",
             "workloads": [TRAIN]})
    for d in ("configs", "traffic", "limits"):
        (root / "bench" / d).mkdir(parents=True, exist_ok=True)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    wmt = _load("configs", "transformer-wmt.json")
    wmt["model"].update(TINY_MODEL)
    wmt["deployment"]["nodes_per_chip"] = 2
    wmt["reference"]["micro_batch"] = 2
    olmo = _load("configs", "olmo-1b.json")
    olmo["model"].update(TINY_MODEL)
    olmo["deployment"]["engine"].update(
        max_slots=4, prompt_len=48, max_new_tokens=16, cache_size=64,
        queue_depth=16, prefill_chunk=16)
    olmo["reference"]["check_tokens"] = 40
    olmo["weights"]["embed_std"] = 0.02     # the tiny limits' own scale
    sw = _load("traffic", "swarm-32x512.json")
    sw.update(local_batch=4, seq_len=16)
    chat = _load("traffic", "chat-poisson.json")
    chat.update(rate_rps=20.0, warmup_s=0.5, drain_s=20.0,
                prompt={"median": 12, "sigma": 0.9, "min": 4, "max": 48},
                output={"median": 6, "sigma": 0.7, "min": 2, "max": 16})
    for name, obj in (("configs/transformer-wmt", wmt),
                      ("configs/olmo-1b", olmo),
                      ("traffic/swarm-32x512", sw),
                      ("traffic/chat-poisson", chat)):
        (root / "bench" / f"{name}.json").write_text(json.dumps(obj))
    for cell, lim in limits.items():
        (root / "bench" / "limits" / f"{cell}.json").write_text(
            json.dumps(lim))
    return root
