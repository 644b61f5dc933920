"""Tiny cells for CPU tests: the real configurations and traffic with
their sizes cut, one chip each, written into a scratch root that the
harness reads by name (the real metric lists of BENCHMARK.json).

A tiny cell is data: its name, the configuration it cuts
(bench/configs/<config>.json), its traffic, and the keys it cuts, merged
into the configuration key by key. `write_root` writes the two cells
below and any further ones given to it in the same form."""
from __future__ import annotations

import json
import shutil
from dataclasses import dataclass
from pathlib import Path

from bench.harness import BENCH

TRAIN, SERVE = "wmt-swarm-q8-4chip", "olmo-serve-chat"
TINY_MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
              "d_ff": 128, "vocab_size": 256}


@dataclass(frozen=True)
class Tiny:
    name: str           # the cell
    config: str         # the configuration it cuts, bench/configs/<config>
    traffic: str        # bench/traffic/<traffic>, cut by TRAFFIC_CUTS
    cut: dict           # merged into the configuration (see `merged`)


TRAIN_CUT = {"model": TINY_MODEL, "deployment": {"nodes_per_chip": 2},
             "reference": {"micro_batch": 2}}
SERVE_CUT = {"model": TINY_MODEL,
             "deployment": {"engine": {
                 "max_slots": 4, "prompt_len": 48, "max_new_tokens": 16,
                 "cache_size": 64, "queue_depth": 16, "prefill_chunk": 16}},
             "reference": {"check_tokens": 40},
             # the tiny limits' own scale
             "weights": {"embed_std": 0.02}}
CELLS = (Tiny(TRAIN, "transformer-wmt", "swarm-32x512", TRAIN_CUT),
         Tiny(SERVE, "olmo-1b", "chat-poisson", SERVE_CUT))
TRAFFIC_CUTS = {
    "swarm-32x512": {"local_batch": 4, "seq_len": 16},
    "chat-poisson": {
        "rate_rps": 20.0, "warmup_s": 0.5, "drain_s": 20.0,
        "prompt": {"median": 12, "sigma": 0.9, "min": 4, "max": 48},
        "output": {"median": 6, "sigma": 0.7, "min": 2, "max": 16}},
}


def _load(*parts):
    return json.loads((BENCH.joinpath(*parts)).read_text())


def merged(base: dict, cut: dict) -> dict:
    """`base` with `cut` applied: a dict under a key that holds a dict is
    merged into it, any other value replaces what is there."""
    out = dict(base)
    for k, v in cut.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else v
    return out


def write_root(root: Path, limits: dict, cells=()) -> Path:
    """A checkout-like root holding the two tiny cells and `cells`
    (further `Tiny`s), each with its cut configuration written under the
    cell's own name, beside a copy of bench/reference; `limits` maps each
    cell to the limits of its numbers. A further cell is listed in every
    metric that lists a tiny cell of its traffic."""
    root = Path(root)
    cells = CELLS + tuple(cells)
    bench = _load("..", "BENCHMARK.json")
    bench["workloads"] = [{"name": c.name, "config": c.name,
                           "traffic": c.traffic, "chips": 1, "why": "tiny"}
                          for c in cells]
    if not any(m["name"] == "train_tokens_per_s" for m in bench["end_to_end"]):
        bench["end_to_end"].append(
            {"name": "train_tokens_per_s", "unit": "tokens/s",
             "better": "higher", "bound": 0.05, "source": "host_clock",
             "workloads": [TRAIN]})
    for c in cells[len(CELLS):]:
        like = {b.name for b in CELLS if b.traffic == c.traffic}
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like & set(m.get("workloads", ())):
                m["workloads"].append(c.name)
    for d in ("configs", "traffic", "limits"):
        (root / "bench" / d).mkdir(parents=True, exist_ok=True)
    shutil.copytree(BENCH / "reference", root / "bench" / "reference",
                    ignore=shutil.ignore_patterns("__pycache__"),
                    dirs_exist_ok=True)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    for c in cells:
        conf = merged(_load("configs", f"{c.config}.json"), c.cut)
        (root / "bench" / "configs" / f"{c.name}.json").write_text(
            json.dumps(conf))
    for name in {c.traffic for c in cells}:
        tr = merged(_load("traffic", f"{name}.json"),
                    TRAFFIC_CUTS.get(name, {}))
        (root / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(tr))
    for cell, lim in limits.items():
        (root / "bench" / "limits" / f"{cell}.json").write_text(
            json.dumps(lim))
    return root
