"""Swarm training job: the program's training entry
(`repro.launch.train.build_trainer`) driven superstep by superstep.

Set-up builds the trainer once (one swarm node per chip over the
`ppermute_pool` transport, or every node vmapped on one chip through
`gather`), gives it weights made on the device from the seed, compiles
its superstep, and drives that same compiled step and state through its
first three supersteps, reading the loss of each, the momentum after the
first and the parameters' change after the third. Two more timed
supersteps size the window.

The window then feeds a host ring of pre-made token batches with
`place_nodes` every superstep and dispatches the compiled superstep,
with no host sync, until one `block_until_ready` on the swarm state
after the last. `train_tokens_per_s` is every token that every node's
local steps consumed in the window, over the window.

Once the window has closed and the program's state is freed, the plain
reference that the configuration names (bench/reference) follows the
same three supersteps from the same weights, batches and pairings, and
the numbers of bench/check.py compare the two.
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

from bench import check, counts
from bench.harness import model_config
from bench.stats import peak_bytes
from bench.trace import span
from bench.traffic import seed32, token_ring

FIRST_STEPS = 3
TIMED_STEPS = 2
# the trainer is built from this seed, not the run's: the matchings it
# draws are compiled into the superstep, which every run then finds in
# the compilation cache; weights, batches and schedule follow the run's
BUILD_SEED = 0
# its set-up makes dozens of small eager compiles (bench.harness)
CACHE_EVERY_PROGRAM = True
REFERENCE_NEEDS = ("init_params", "loss_and_grad")


def _matching_pairs(perm) -> list:
    perm = [int(x) for x in perm]
    if sorted(perm) != list(range(len(perm))) or \
            any(perm[perm[i]] != i for i in range(len(perm))):
        raise ValueError(f"pairing {perm} is not a matching")
    return [(i, j) for i, j in enumerate(perm) if i < j]


class Swarm:
    """The compiled superstep with its state, re-seedable in one process."""

    def __init__(self, cell, devices, build_seed: int):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.exchange import make_matching_pool
        from repro.core.graph import make_graph
        from repro.launch.mesh import node_mesh
        from repro.launch.train import build_trainer

        self.cell, self.devices = cell, devices
        conf, self.traffic = cell.config, cell.traffic
        self.dep = dep = conf["deployment"]
        self.model = conf["model"]
        self.cfg = model_config(conf)
        self.n_nodes = dep["nodes_per_chip"] * len(devices)
        self.H = dep["H"]
        self.impl = "ppermute_pool" if len(devices) > 1 else "gather"
        quantize = dep["wire"] != "fp32"
        s32 = seed32(build_seed)
        self.step, state, self.scfg, self.graph = build_trainer(
            self.cfg, "swarm", self.n_nodes, self.H, dep["lr"],
            quantize=quantize, nonblocking=not dep["blocking"], seed=s32,
            momentum=dep["momentum"],
            gossip_impl=self.impl, pool_size=dep["pool_size"],
            codec=dep["wire"] if quantize else None, devices=devices)
        self.pool = None
        if self.impl == "ppermute_pool":
            # the matchings the transport compiled in: the schedule, an
            # input to the reference like the token batches
            self.pool = make_matching_pool(
                make_graph("complete", self.n_nodes), K=dep["pool_size"],
                seed=s32)
        self.mesh = node_mesh(self.n_nodes, devices)
        self.repl = NamedSharding(self.mesh, P()) if self.mesh is not None \
            else jax.sharding.SingleDeviceSharding(devices[0])
        self.shardings = jax.tree.map(lambda x: x.sharding, state)
        self.shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
            state.params)
        self.opt_struct = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state.opt)
        self.has_prev = state.prev is not None
        del state
        gc.collect()
        dtype = jnp.dtype(self.cfg.dtype)
        n = self.n_nodes
        shp = self.shapes
        ref, weights = cell.reference, conf.get("weights", {})
        self.make_weights = jax.jit(
            lambda key: ref.init_params(key, shp, dtype, **weights))

        def stacked(p):
            tree = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n,) + x.shape), p)
            return tree, jax.tree.map(jnp.copy, tree)
        psh = self.shardings.params
        self.stack = jax.jit(stacked, out_shardings=(psh, psh))
        self.zero_opt = jax.jit(
            lambda: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                 self.opt_struct),
            out_shardings=self.shardings.opt)

        def node_norms(tree):
            return jnp.stack([jnp.sqrt(jnp.sum(
                jnp.square(x.astype(jnp.float32)),
                axis=tuple(range(1, x.ndim)))) for x in jax.tree.leaves(tree)],
                axis=1)
        self.norms = jax.jit(node_norms)
        self.change = jax.jit(lambda p, p0: node_norms(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            p, p0)))
        self.compiled = None

    # -- inputs from the seed ----------------------------------------------

    def weights(self, seed: int):
        import jax
        return self.make_weights(jax.random.PRNGKey(seed32(seed)))

    def reseed(self, seed: int):
        """Fresh state, batches and schedule from `seed`."""
        import jax
        import jax.numpy as jnp
        from repro.core.swarm import SwarmState
        self.state = None
        gc.collect()
        params, prev = self.stack(self.weights(seed))
        self.state = SwarmState(
            params, self.zero_opt(), prev if self.has_prev else None,
            jax.device_put(jnp.zeros((), jnp.int32), self.shardings.step))
        self.ring = token_ring(self.traffic, self.n_nodes, self.H,
                               self.cfg.vocab_size, seed)
        self.seed = seed
        self._rng = np.random.default_rng(seed)
        self.perms_np = np.zeros((0, self.n_nodes), np.int32)
        self.rows = []
        self.extend(FIRST_STEPS + TIMED_STEPS)

    def extend(self, n_rows: int):
        """Schedule rows (pairing, local-step counts, key) on the device
        for supersteps up to n_rows."""
        import jax
        from repro.launch.train import presample_inputs
        have = len(self.rows)
        if n_rows <= have:
            return
        perms, hs = presample_inputs(self.scfg, self.graph, self._rng,
                                     seed32(self.seed), n_rows - have)
        keys = np.asarray(jax.random.split(
            jax.random.PRNGKey(seed32(self.seed + have + 1)), n_rows - have))
        self.perms_np = np.concatenate([self.perms_np, perms])
        for p, h, k in zip(perms, hs, keys):
            self.rows.append(tuple(jax.device_put(a, self.repl)
                                   for a in (p, h, k)))

    def pairs(self, t: int) -> list:
        perm = self.perms_np[t]
        if self.pool is not None:
            perm = self.pool[int(perm[0])]
        return _matching_pairs(perm)

    # -- the timed path ----------------------------------------------------

    def feed(self, t: int):
        from repro.launch.train import place_nodes
        return place_nodes(self.ring[t % len(self.ring)], self.mesh)

    def compile(self):
        self.compiled = self.step.lower(self.state, self.feed(0),
                                        *self.rows[0]).compile()
        return self.compiled

    def superstep(self, t: int):
        with span("train.feed"):
            batch = self.feed(t)
        with span("train.dispatch"):
            self.state, m = self.compiled(self.state, batch, *self.rows[t])
        return m

    def first_steps(self) -> dict:
        """The first supersteps through the timed path, with the readings
        the check compares."""
        p0 = self.state.params
        losses, mom = [], None
        for t in range(FIRST_STEPS):
            m = self.superstep(t)
            losses.append(float(m["loss"]))
            if t == 0:
                mom = np.asarray(self.norms(self.state.opt))
        change = np.asarray(self.change(self.state.params, p0))
        return {"losses": losses, "mom_norms": mom, "change_norms": change}

    # -- the reference -----------------------------------------------------

    def reference(self, prec: str = "f32") -> dict:
        from bench.reference import swarm as ref_swarm
        return ref_swarm.run(
            self.cell.reference, self.model, self.weights(self.seed),
            self.ring[:FIRST_STEPS],
            [self.pairs(t) for t in range(FIRST_STEPS)],
            lr=self.dep["lr"], mu=self.dep["momentum"], devices=self.devices,
            micro=self.cell.config["reference"]["micro_batch"], prec=prec)

    def free(self):
        self.state = None
        self.rows = []
        gc.collect()


def run(ctx) -> dict:
    import jax
    cell = ctx.cell
    sw = Swarm(cell, ctx.devices, BUILD_SEED)
    ctx.phase("build_trainer")
    sw.reseed(ctx.seed)
    ctx.phase("weights_and_feed")
    sw.compile()
    ctx.phase("compile")
    prog = sw.first_steps()
    ctx.phase("first_steps")
    t_a = time.time()
    for t in range(FIRST_STEPS, FIRST_STEPS + TIMED_STEPS):
        sw.superstep(t)
    jax.block_until_ready(sw.state)
    est = (time.time() - t_a) / TIMED_STEPS
    n_win = max(3, int(math.ceil(ctx.seconds / est)))
    t0 = FIRST_STEPS + TIMED_STEPS
    sw.extend(t0 + n_win)

    t_w0 = ctx.begin_window()
    for t in range(t0, t0 + n_win):
        sw.superstep(t)
    with span("train.block"):
        jax.block_until_ready(sw.state)
    t_w1 = ctx.end_window()

    window_s = t_w1 - t_w0
    tr = cell.traffic
    tokens = n_win * sw.n_nodes * sw.H * tr["local_batch"] * tr["seq_len"]
    mem = peak_bytes(ctx.devices)
    sw.free()
    t_ref = time.time()
    ref = sw.reference()
    ctx.phase(f"reference {time.time() - t_ref:.3f}s, after window")
    numbers = check.train_numbers(prog, ref)
    return {
        "setup_s": t_w0 - ctx.t_start,
        "window_s": window_s,
        "e2e": {"train_tokens_per_s": tokens / window_s},
        "attempted": n_win, "failed": 0,
        "numbers": numbers,
        "memory_peak_bytes": mem,
        "readings": {
            "tokens": tokens, "window_s": window_s, "supersteps": n_win,
            "chips": len(ctx.devices), "nodes": sw.n_nodes,
            "flops_per_token": counts.train_flops_per_token(
                cell.config["model"], tr["seq_len"]),
            "losses": prog["losses"], "ref_losses": ref["losses"]},
    }
