"""Reference replay of served requests: how far below the reference's best
logit each served token lies."""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp

from bench.reference import transformer as T


@lru_cache(maxsize=None)
def gap_fn(model_items: tuple, pad_to: int, rows: int, prec: str):
    """jit(params, seq[pad_to], start, targets[rows]) -> gaps[rows].

    Runs the float32 reference over the padded sequence (causal, so the
    padding after the served tokens changes nothing before it) and reads
    the logits of positions start .. start+rows-1, which predict the
    served tokens. prec="f32": the gap of each target. prec="fp8": the
    gap of the token a float8 reference puts first (the control).
    prec="margin": the reference's own best less its second best, the
    room a sound token has before it would be swapped."""
    model = dict(model_items)

    @jax.jit
    def fn(params, seq, start, targets):
        h = T.hidden(model, params, seq[None, :])
        h = jax.lax.dynamic_slice_in_dim(h[0], start, rows, axis=0)
        ref = T.logits(model, params, h)
        if prec == "margin":
            top = jax.lax.top_k(ref, 2)[0]
            return top[:, 0] - top[:, 1]
        if prec == "f32":
            pick = targets
        else:
            hc = T.hidden(model, params, seq[None, :], prec)
            hc = jax.lax.dynamic_slice_in_dim(hc[0], start, rows, axis=0)
            pick = jnp.argmax(T.logits(model, params, hc, prec), axis=-1)
        best = jnp.max(ref, axis=-1)
        got = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
        return best - got
    return fn
