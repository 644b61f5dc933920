"""Readings that the correctness limits are set from, for one cell, in one
process on the chip at the cell's own size:

* the program's numbers on each of `--seeds` (the lower reading is the
  largest of them);
* the control's on each of `--control-seeds`: the reference itself put in
  the program's place, computed in float8 (the upper reading is the
  smallest). The reference is the module the configuration names, as
  `bench.harness.load_cell` finds it for a run;
* each planted fault's (bench/faults.py) on each of `--fault-seeds`.

Each reading is also judged by the harness's own comparison
(`bench.check.judge`) against the cell's committed limits.

    python3 bench/tools/calibrate.py --workload olmo-serve-chat \
        --seeds 1,2,3 --control-seeds 1,2,3 \
        --faults altered_token --fault-seeds 1,2,3

Prints one JSON line per reading. Serving cells drive each seed's traffic
for `--seconds` at the cell's rate, then compare as a run does.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def _ints(s: str):
    return [int(x) for x in s.split(",") if x]


def emit(cell, numbers, n_failed=0, **kw):
    """One reading, with its numbers judged against the cell's limits."""
    from bench.check import judge
    from bench.harness import _plain
    correct, _ = judge(numbers, cell.limits, n_failed)
    print(json.dumps(_plain(dict(kw, correct=correct, **numbers))),
          flush=True)


def worst_leaves(prog, ref, names) -> dict:
    """Where each norm gap is worst (node, leaf) and its median over the
    leaves: which leaf sets a reading, and whether the others agree."""
    import numpy as np
    from bench import check
    out = {}
    for key, keep in (("mom_norms", None),
                      ("change_norms", check.moving_leaves(ref["grad_norms"]))):
        gap = check.leaf_gaps(prog[key], ref[key], keep)
        node, leaf = np.unravel_index(int(np.argmax(gap)), gap.shape)
        out[key[:-6]] = {"worst": [int(node), names[leaf]],
                         "median": float(np.median(gap))}
    return out


def train(cell, devices, args):
    import jax
    from bench import check, faults
    from bench.jobs.train import BUILD_SEED, Swarm
    refs = {}

    def reference(sw, seed):
        if seed not in refs:
            sw.free()
            refs[seed] = sw.reference()
        return refs[seed]

    sw = Swarm(cell, devices, BUILD_SEED)
    names = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_flatten_with_path(sw.shapes)[0]]
    for seed in args.seeds:
        sw.reseed(seed)
        if sw.compiled is None:
            sw.compile()
        t = time.time()
        prog = sw.first_steps()
        ref = reference(sw, seed)
        emit(cell, check.train_numbers(prog, ref), kind="program", seed=seed,
             losses=prog["losses"], ref_losses=ref["losses"],
             wall_s=time.time() - t, **worst_leaves(prog, ref, names))
    for seed in args.control_seeds:
        sw.reseed(seed)
        sw.free()
        ref = reference(sw, seed)
        ctrl = sw.reference("fp8")
        emit(cell, check.train_numbers(ctrl, ref), kind="control", seed=seed,
             losses=ctrl["losses"], ref_losses=ref["losses"],
             **worst_leaves(ctrl, ref, names))
    del sw
    gc.collect()
    for name in args.faults:
        with faults.FAULTS[name]():
            fsw = Swarm(cell, devices, BUILD_SEED)   # same pool
            for seed in args.fault_seeds:
                fsw.reseed(seed)
                if fsw.compiled is None:
                    fsw.compile()
                prog = fsw.first_steps()
                ref = reference(fsw, seed)
                emit(cell, check.train_numbers(prog, ref),
                     kind=f"fault:{name}", seed=seed, losses=prog["losses"],
                     ref_losses=ref["losses"],
                     **worst_leaves(prog, ref, names))
            fsw.free()
            del fsw
            gc.collect()


def serve(cell, devices, args):
    import numpy as np
    from bench import faults
    from bench.jobs.serve import (Server, sample_for_check, served_gaps,
                                  window_stats)
    from bench.traffic import chat_requests
    tr = cell.traffic
    ref, model = cell.reference, cell.config["model"]

    def one(seed, kinds):
        srv = Server(cell, devices[0], seed)
        srv.prewarm()
        reqs = chat_requests(tr, srv.cfg.vocab_size,
                             [tr["warmup_s"], args.seconds, 1.0], seed)
        d = srv.drive(reqs, tr["warmup_s"], args.seconds, tr["drain_s"])
        st = window_stats(d)
        srv.free()
        sample = sample_for_check(d, seed,
                                  cell.config["reference"]["check_tokens"])
        w = srv.weights()
        for kind in kinds:
            prec = "fp8" if kind == "control" else "f32"
            g = served_gaps(ref, model, w, sample, d, srv.ecfg.kv_capacity,
                            srv.ecfg.max_new_tokens, prec)
            extra = {}
            if kind == "program":
                m = served_gaps(ref, model, w, sample, d,
                                srv.ecfg.kv_capacity,
                                srv.ecfg.max_new_tokens, "margin")
                extra = {f"margin_p{q}": float(np.percentile(m, q))
                         for q in (1, 10, 50)}
            emit(cell, {"logit_gap": float(g.max())},
                 0 if kind == "control" else st["failed"],
                 kind=kind, seed=seed, checked_tokens=int(g.size),
                 checked_requests=len(sample), **extra, **st)
        del srv, w
        gc.collect()

    for seed in args.seeds:
        one(seed, ["program"] + (["control"] if seed in args.control_seeds
                                 else []))
    for seed in args.control_seeds:
        if seed not in args.seeds:
            one(seed, ["control"])
    for name in args.faults:
        with faults.FAULTS[name]():
            for seed in args.fault_seeds:
                one(seed, [f"fault:{name}"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, required=True)
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--faults", type=lambda s: [x for x in s.split(",") if x],
                    default=[])
    ap.add_argument("--fault-seeds", type=_ints, default=[])
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    from bench.harness import load_cell, use_compile_cache
    cell = load_cell(args.workload)
    use_compile_cache(cell)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print("calibrate: needs the chips the cell asks for", file=sys.stderr)
        return 3
    (train if cell.job == "train" else serve)(cell, devs[:cell.chips], args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
