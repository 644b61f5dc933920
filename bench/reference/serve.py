"""Reference replay of served requests: how far below the reference's best
logit each served token lies."""
from __future__ import annotations

import json
from functools import lru_cache

import jax
import jax.numpy as jnp

from bench.reference import model_key


def gap_fn(ref, model: dict, pad_to: int, rows: int, prec: str):
    """jit(params, seq[pad_to], start, targets[rows]) -> gaps[rows], one
    per reference module, `model`, size and precision.

    Runs the float32 reference `ref` (a module, see bench/reference) with
    the configuration's `model` dict over the padded sequence (causal, so
    the padding after the served tokens changes nothing before it) and reads
    the logits of positions start .. start+rows-1, which predict the
    served tokens. prec="f32": the gap of each target. prec="fp8": the
    gap of the token a float8 reference puts first (the control).
    prec="margin": the reference's own best less its second best, the
    room a sound token has before it would be swapped."""
    return _gap_fn(ref, model_key(model), pad_to, rows, prec)


@lru_cache(maxsize=None)
def _gap_fn(ref, key: str, pad_to: int, rows: int, prec: str):
    model = json.loads(key)

    @jax.jit
    def fn(params, seq, start, targets):
        h = ref.hidden(model, params, seq[None, :], "f32")
        h = jax.lax.dynamic_slice_in_dim(h[0], start, rows, axis=0)
        lg = ref.logits(model, params, h, "f32")
        if prec == "margin":
            top = jax.lax.top_k(lg, 2)[0]
            return top[:, 0] - top[:, 1]
        if prec == "f32":
            pick = targets
        else:
            hc = ref.hidden(model, params, seq[None, :], prec)
            hc = jax.lax.dynamic_slice_in_dim(hc[0], start, rows, axis=0)
            pick = jnp.argmax(ref.logits(model, params, hc, prec), axis=-1)
        best = jnp.max(lg, axis=-1)
        got = jnp.take_along_axis(lg, pick[:, None], axis=-1)[:, 0]
        return best - got
    return fn
