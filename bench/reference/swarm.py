"""Plain reference of SwarmSGD's first supersteps (blocking, fixed H):
every node takes H momentum-SGD steps on its own batches, then each
matched pair averages its models exactly.

State is kept as the configuration states it: parameters in their
stored dtype (the update is computed in float32 and rounded once, as a
momentum-SGD step on bfloat16 weights does), momentum in float32. The
exchange is exact: the reference has no wire format, so the program's
quantized wire shows as a difference from it.

Node i lives on devices[i % len(devices)]; calls on different devices
run side by side.
"""
from __future__ import annotations

import json
from functools import partial

import jax
import jax.numpy as jnp

from bench.reference import model_key

F32 = jnp.float32


def leaf_norms(tree) -> jnp.ndarray:
    """L2 norm of each leaf, in float32, in tree-leaf order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
                      for x in jax.tree.leaves(tree)])


@partial(jax.jit,
         static_argnames=("ref", "model", "micro", "prec", "lr", "mu"))
def _local_steps(params, mom, tokens, targets, *, ref, model, micro, prec,
                 lr, mu):
    """H steps on one node's [H, B, S] batch: (params, momentum, mean loss
    over the steps, leaf norms of the first gradient)."""
    mdl = json.loads(model)
    losses, g1 = [], None
    for q in range(tokens.shape[0]):
        l, g = ref.loss_and_grad(mdl, params, tokens[q], targets[q], micro,
                                 prec)
        if g1 is None:
            g1 = leaf_norms(g)
        mom = jax.tree.map(lambda m, gg: mu * m + gg, mom, g)
        params = jax.tree.map(lambda p, m: (p.astype(F32) - lr * m)
                              .astype(p.dtype), params, mom)
        losses.append(l)
    return params, mom, jnp.mean(jnp.stack(losses)), g1


@jax.jit
def _average(a, b):
    return jax.tree.map(lambda x, y: ((x.astype(F32) + y.astype(F32)) * 0.5)
                        .astype(x.dtype), a, b)


@jax.jit
def _change_norms(p, p0):
    return leaf_norms(jax.tree.map(lambda a, b: a.astype(F32) - b.astype(F32),
                                   p, p0))


def run(ref, model: dict, params0, batches, pairs, *, lr: float, mu: float,
        devices, micro: int, prec: str = "f32") -> dict:
    """Follow len(batches) supersteps of the reference module `ref` (see
    bench/reference) with the configuration's `model` dict from `params0`
    (one node's tree, the same for every node). `batches[t]` holds numpy
    {"tokens", "targets"} of shape [n_nodes, H, B, S]; `pairs[t]` the
    matched node pairs of superstep t. Returns per-superstep mean losses,
    and per node and leaf: the momentum norms after the first superstep,
    the first gradient's norms, and the norms of the parameters' change
    after the last."""
    n = batches[0]["tokens"].shape[0]
    mdl = model_key(model)
    dev = [devices[i % len(devices)] for i in range(n)]
    p = [jax.device_put(params0, dev[i]) for i in range(n)]
    m = [jax.tree.map(lambda x: jnp.zeros(x.shape, F32), p[i])
         for i in range(n)]
    losses, mom1, grad1 = [], None, None
    for t, batch in enumerate(batches):
        out = []
        for i in range(n):
            out.append(_local_steps(
                p[i], m[i], jax.device_put(batch["tokens"][i], dev[i]),
                jax.device_put(batch["targets"][i], dev[i]), ref=ref,
                model=mdl, micro=micro, prec=prec, lr=lr, mu=mu))
            p[i] = m[i] = None
            if len(set(dev)) < n:       # nodes share a chip: one at a time
                jax.block_until_ready(out[-1])
        p = [o[0] for o in out]
        m = [o[1] for o in out]
        losses.append(float(jnp.mean(jnp.stack(
            [jax.device_put(o[2], dev[0]) for o in out]))))
        if t == 0:
            mom1 = [jax.device_get(leaf_norms(m[i])) for i in range(n)]
            grad1 = [jax.device_get(o[3]) for o in out]
        for a, b in pairs[t]:
            avg = _average(p[a], jax.device_put(p[b], dev[a]))
            p[a], p[b] = avg, jax.device_put(avg, dev[b])
    change = [jax.device_get(_change_norms(p[i], jax.device_put(params0,
                                                                 dev[i])))
              for i in range(n)]
    return {"losses": losses, "mom_norms": mom1, "grad_norms": grad1,
            "change_norms": change}
