"""Plain float32 reference of the repo's decoder stack (attention + dense
MLP layers, pre-norm, RoPE, tied embedding), written from the layer
equations with no cache, no kernels and no batching tricks.

It reads parameters in the program's layout (`embed`, `final_norm`,
`blocks/layer_0/{norm1, attn/{wq,wk,wv,wo}, norm2, mlp/{w_up, w_down,
w_gate}}`, the blocks stacked on a leading layer axis) but imports nothing
of the program. Every matrix product runs in float32 at the highest
precision. `prec="fp8"` is the control: every matrix product's operands
are rounded to float8 e4m3 with one scale per tensor (the step below the
bfloat16 the configurations state), with straight-through gradients.

Departures of the program's model from the published ones are the
program's; the reference follows the program's model as configured
(bench/configs/*.json, "model"): embeddings scaled by sqrt(d_model),
norm epsilon 1e-6, GELU in its tanh form, RoPE on interleaved pairs.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0
EPS = 1e-6


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def init_params(key, shapes, dtype, embed_std: float = 0.02):
    """Weights for the given layout from one key: norm scales 1, norm
    biases 0, the embedding N(0, embed_std^2) (the configuration's
    `weights.embed_std`), every other matrix N(0, 1/fan_in) with fan_in
    its second-to-last axis. Jit it: the weights are then made on the
    device in one call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(key, len(flat))
    out = []
    for (path, s), k in zip(flat, keys):
        name = _leaf_name(path)
        if name == "scale":
            x = jnp.ones(s.shape, F32)
        elif name == "bias":
            x = jnp.zeros(s.shape, F32)
        else:
            std = embed_std if name == "embed" else s.shape[-2] ** -0.5
            x = jax.random.normal(k, s.shape, F32) * std
        out.append(x.astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------

def fake_fp8(x):
    """x rounded to float8 e4m3 under one per-tensor scale, gradient
    passed straight through."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    return x + jax.lax.stop_gradient(q - x)


def mm(spec, a, b, prec):
    a, b = a.astype(F32), b.astype(F32)
    if prec == "fp8":
        a, b = fake_fp8(a), fake_fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def norm(model, p, x):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    y = (x - mean) / jnp.sqrt(var + EPS)
    if model["norm"] == "layernorm":
        y = y * p["scale"].astype(F32) + p["bias"].astype(F32)
    elif model["norm"] != "nonparam_ln":
        raise ValueError(f"reference has no norm {model['norm']!r}")
    return y


def rope(x, theta):
    """x: [B, S, H, hd]; rotate interleaved pairs (x[2i], x[2i+1]) of each
    head by position * theta^(-2i/hd)."""
    S, hd = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv            # [S, hd/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def layer(model, p, x, prec):
    B, S, d = x.shape
    H = model["n_heads"]
    hd = d // H
    h = norm(model, p.get("norm1", {}), x)
    a = p["attn"]
    q = mm("bsd,dh->bsh", h, a["wq"], prec).reshape(B, S, H, hd)
    k = mm("bsd,dh->bsh", h, a["wk"], prec).reshape(B, S, H, hd)
    v = mm("bsd,dh->bsh", h, a["wv"], prec).reshape(B, S, H, hd)
    q, k = rope(q, model["rope_theta"]), rope(k, model["rope_theta"])
    s = mm("bqhd,bkhd->bhqk", q, k, prec) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = mm("bhqk,bkhd->bqhd", w, v, prec).reshape(B, S, H * hd)
    x = x + mm("bsh,hd->bsd", o, a["wo"], prec)
    h = norm(model, p.get("norm2", {}), x)
    f = p["mlp"]
    up = mm("bsd,df->bsf", h, f["w_up"], prec)
    if model["gated_mlp"]:
        g = mm("bsd,df->bsf", h, f["w_gate"], prec)
        act = jax.nn.sigmoid(g) * g * up
    elif model["act"] == "gelu":
        act = gelu_tanh(up)
    else:
        act = jax.nn.sigmoid(up) * up
    return x + mm("bsf,fd->bsd", act, f["w_down"], prec)


def hidden(model, params, tokens, prec="f32", remat=False):
    """Final normed hidden states [B, S, d] of token ids [B, S]. `remat`
    keeps only each layer's input for the backward pass."""
    x = params["embed"].astype(F32)[tokens] * math.sqrt(model["d_model"])
    blocks = params["blocks"]["layer_0"]

    def body(x, p):
        return layer(model, p, x, prec), None
    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, blocks)
    return norm(model, params.get("final_norm", {}), x)


def logits(model, params, h, prec="f32"):
    return mm("...d,vd->...v", h, params["embed"], prec)


def loss(model, params, tokens, targets, prec="f32"):
    """Mean next-token cross-entropy over every position."""
    lg = logits(model, params, hidden(model, params, tokens, prec,
                                      remat=True), prec)
    lse = jax.nn.logsumexp(lg, axis=-1)
    tgt = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt)


def loss_and_grad(model, params, tokens, targets, micro: int, prec="f32"):
    """Loss and float32 gradient over a [B, S] batch, taken in blocks of
    `micro` rows so the activations fit; the mean over all rows."""
    p32 = jax.tree.map(lambda a: a.astype(F32), params)
    B = tokens.shape[0]
    n = B // micro
    tb = tokens.reshape(n, micro, -1)
    gb = targets.reshape(n, micro, -1)
    vg = jax.value_and_grad(lambda p, t, y: loss(model, p, t, y, prec))

    def body(acc, xs):
        l, g = vg(p32, *xs)
        return jax.tree.map(lambda a, b: a + b / n, acc,
                            (l, g)), None
    zero = (jnp.zeros((), F32), jax.tree.map(jnp.zeros_like, p32))
    (l, g), _ = jax.lax.scan(body, zero, (tb, gb))
    return l, g
