"""Run one cell of the benchmark once.

A cell is an entry of `workloads` in BENCHMARK.json: a configuration and
a traffic mix on 1 or 4 chips. Everything else is found by name:

    bench/configs/<config>.json    model, deployment, source, cuts
    bench/reference/<module>.py    the configuration's plain reference,
                                   named by its `reference.module`
    bench/traffic/<traffic>.json   the job and its traffic parameters
    bench/limits/<cell>.json       the limit of each number compared
    bench/jobs/<job>.py            run(ctx) -> result of one run
    bench/metrics/<metric>.py      read(r) -> a per-layer metric, or None

A reference module imports nothing of the program and offers, with
`model` the configuration's own `model` dict and `prec` "f32" or "fp8"
(the control):

    init_params(key, shapes, dtype, **config.get("weights", {}))
    hidden(model, params, tokens, prec)     final normed hidden states
    logits(model, params, h, prec)          logits of hidden states
    loss_and_grad(model, params, tokens, targets, micro, prec)
                                            training only

A job lists the functions it calls in `REFERENCE_NEEDS`; a cell whose
module is missing, or lacks one of them, is refused when it is loaded
(exit 2, before set-up).

The harness checks the device (a TPU, as many chips as the cell asks
for, listed in bench/peaks.json), keeps JAX's compilation cache in
`<checkout>/.jax_cache`, runs the job, and with `--trace 1` takes the
profiler trace of the measured window and hands it to the readers.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


# ---------------------------------------------------------------------------
# Cells, found by name
# ---------------------------------------------------------------------------

@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    reference: Any              # the module bench/reference/<module>.py

    @property
    def job(self) -> str:
        return self.traffic["job"]


def _json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell `name` of `<root>/BENCHMARK.json` with its data files and
    its configuration's reference module."""
    bench = _json(Path(root) / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    data = Path(root) / "bench"
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"] in moved
                                  else [])]
    config = _json(data / "configs" / f"{w['config']}.json")
    traffic = _json(data / "traffic" / f"{w['traffic']}.json")
    needs = getattr(load_module("jobs", traffic["job"]), "REFERENCE_NEEDS",
                    ())
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                limits=_json(data / "limits" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer,
                reference=load_reference(config, needs, data))


def load_reference(config: dict, needs=(), base: Path = BENCH):
    """The reference module `<base>/reference/<module>.py` that the
    configuration names (`reference.module`), holding each of `needs`.
    Both jobs, and the tools, take their reference from here."""
    name = config["reference"]["module"]
    mod = load_module("reference", name, base)
    missing = [f for f in needs if not callable(getattr(mod, f, None))]
    if missing:
        raise AttributeError(f"reference {name!r} lacks {missing}")
    return mod


def model_config(conf: dict):
    """The program's registered architecture (`arch`) with the
    configuration's `model` keys applied: a no-op at the published
    sizes, the configuration as it is run in any case. A dict under a
    sub-config key (`moe`, `ssm`, `frontend`) is applied to the
    architecture's sub-config, or builds one where it has none; a
    `pattern` list becomes a tuple of (mixer, ffn) tuples. A key that
    is not a field is an error that names it."""
    import dataclasses
    from repro.configs import (FrontendConfig, MoEConfig, SSMConfig,
                               get_config)
    subs = {"moe": MoEConfig, "ssm": SSMConfig, "frontend": FrontendConfig}
    base = get_config(conf["arch"])

    def unknown(keys, cls, where):
        bad = sorted(set(keys) - {f.name for f in dataclasses.fields(cls)})
        if bad:
            raise ValueError(f"model key {where}{bad[0]!r} is not a field "
                             f"of {cls.__name__} ({conf['arch']})")
    unknown(conf["model"], type(base), "")
    changes = {}
    for k, v in conf["model"].items():
        if k in subs and isinstance(v, dict):
            unknown(v, subs[k], f"{k}.")
            sub = getattr(base, k)
            v = subs[k](**v) if sub is None else dataclasses.replace(sub, **v)
        elif k == "pattern":
            v = tuple(tuple(layer) for layer in v)
        changes[k] = v
    return dataclasses.replace(base, **changes)


def load_module(kind: str, name: str, base: Path = BENCH):
    """`<base>/<kind>/<name>.py` as a module (names may hold dots)."""
    path = Path(base) / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind.rstrip('s')} {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def use_compile_cache(cell: Optional[Cell] = None) -> Path:
    """Keep JAX's persistent compilation cache in the checkout, at a fixed
    path. A cell whose job sets `CACHE_EVERY_PROGRAM` keeps every program
    there, so that only its first run compiles: no size cap (a
    machine-wide cap evicts a four-chip cell's programs between its runs)
    and no least compile time (JAX's default of one second leaves out the
    many small eager compiles of that job's set-up)."""
    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(cache))
    if cell is not None and getattr(load_module("jobs", cell.job),
                                    "CACHE_EVERY_PROGRAM", False):
        jax.config.update("jax_compilation_cache_max_size", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache


def peaks_for(device_kind: str) -> dict:
    table = _json(BENCH / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json ({sorted(table)})")
    return table[device_kind]


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

@dataclass
class Context:
    """What a job gets: the cell, its seed and window, its devices, and
    the calls that open and close the measured window."""
    cell: Cell
    seed: int
    seconds: float
    tracing: bool
    devices: list
    t_start: float
    trace_dir: Optional[str] = None
    t_window: List[float] = field(default_factory=list)
    compiles: List[float] = field(default_factory=list)
    phases: List[tuple] = field(default_factory=list)
    _span: Any = None

    def phase(self, name: str):
        """Mark the end of a set-up phase (printed on stderr)."""
        self.phases.append((name, time.time() - self.t_start))

    def begin_window(self) -> float:
        import jax
        from bench.trace import WINDOW_SPAN, span
        if self.tracing:
            jax.profiler.start_trace(self.trace_dir)
        self._span = span(WINDOW_SPAN)
        self._span.__enter__()
        self.t_window[:] = [time.time()]
        return self.t_window[0]

    def end_window(self) -> float:
        import jax
        t = time.time()
        self._span.__exit__(None, None, None)
        self.t_window.append(t)
        if self.tracing:
            jax.profiler.stop_trace()
        return t

    @property
    def in_window(self) -> bool:
        return len(self.t_window) == 1


@dataclass
class Readings:
    """What a per-layer metric's reader gets."""
    cell: Cell
    job: dict                   # the job's result, `readings` inside
    trace: Any                  # bench.trace.Summary of the window
    peaks: dict


def _count_compiles(ctx: Context):
    import jax

    def listener(event, duration, **kw):
        if event == COMPILE_EVENT and ctx.in_window:
            ctx.compiles.append(duration)
    jax.monitoring.register_event_duration_secs_listener(listener)


def run_cell(cell: Cell, seed: int, seconds: float, tracing: bool,
             devices: list, t_start: float, trace_root: Path = ROOT
             ) -> dict:
    """Run the cell once; returns the result object (without printing)."""
    import jax
    trace_dir = str(Path(trace_root) / ".bench_trace" / cell.name)
    if tracing:
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = Context(cell, seed, seconds, tracing, devices, t_start,
                  trace_dir=trace_dir)
    _count_compiles(ctx)
    job = load_module("jobs", cell.job).run(ctx)
    gc.collect()
    from bench.check import judge
    # a program compiled inside the window times the compiler, not the
    # system: such a run is not correct
    correct, checks = judge(
        dict(job["numbers"], compiles_in_window=len(ctx.compiles)),
        dict(cell.limits, compiles_in_window=0), job["failed"])
    dev = devices[0]
    result: Dict[str, Any] = {
        "correct": correct, "attempted": job["attempted"],
        "failed": job["failed"], "metrics": {},
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": job["memory_peak_bytes"]}}
    if tracing:
        from bench import trace as tr
        summary = tr.summarize(tr.load(tr.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        result["device"]["busy_s"] = summary.busy_s
        result["device"]["window_s"] = summary.window_s
        r = Readings(cell, job, summary, peaks_for(dev.device_kind))
        for m in cell.per_layer:
            v = load_module("metrics", m["name"]).read(r)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
        result["breakdown"] = summary.breakdown()
    else:
        for m in cell.end_to_end:
            v = job["setup_s"] if m["name"] == "setup_s" \
                else job["e2e"][m["name"]]
            result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    for name, t in ctx.phases:
        print(f"phase {name} {t:.3f}", file=sys.stderr)
    result["checks"] = checks
    return result


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.time() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"bench: no program at {src}/repro", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    try:
        cell = load_cell(args.workload)
    except (KeyError, FileNotFoundError, AttributeError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    use_compile_cache(cell)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: JAX found no accelerator (platform "
              f"{devs[0].platform!r})", file=sys.stderr)
        return 3
    if len(devs) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        return 3
    try:
        peaks_for(devs[0].device_kind)
    except KeyError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 4
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devs[:cell.chips], t_start)
    for k, c in result["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(_plain(result)), flush=True)
    return 0


def _plain(x):
    """JSON-safe copy: numpy scalars become Python numbers, and a number
    that is not finite becomes null."""
    if isinstance(x, dict):
        return {k: _plain(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_plain(v) for v in x]
    if hasattr(x, "item") and not isinstance(x, (str, bytes)):
        x = x.item()
    if isinstance(x, float) and x != x or x in (float("inf"), float("-inf")):
        return None
    return x
