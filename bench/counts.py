"""Operations and bytes the algorithm needs, from shapes alone.

Kept with the benchmark so that every PR counts the same way. A share of
a peak computed from these counts can only pass 100% if a count is too
high or a time leaves out part of the work.
"""
from __future__ import annotations

import re
from typing import Dict, Sequence

ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
            "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
            "f64": 8, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 0.5, "u4": 0.5}
_SHAPE = re.compile(r"\b(" + "|".join(sorted(ITEMSIZE, key=len, reverse=True))
                    + r")\[([\d,]*)\]")


def param_count(model: dict) -> int:
    """Parameters of the repo's decoder stack (tied embedding counted once,
    as the output head): attention q/k/v/o, the MLP, and the norms."""
    d, L, V = model["d_model"], model["n_layers"], model["vocab_size"]
    hd = model.get("head_dim") or d // model["n_heads"]
    attn = d * model["n_heads"] * hd * 2 + d * model["n_kv_heads"] * hd * 2
    mlp = (3 if model["gated_mlp"] else 2) * d * model["d_ff"]
    norm = {"layernorm": 2 * d, "rmsnorm": d, "nonparam_ln": 0}[model["norm"]]
    head = 0 if model.get("tie_embeddings", True) else V * d
    return V * d + head + L * (attn + mlp + 2 * norm) + norm


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """Model FLOP of one trained token, forward and backward, with no
    recomputation: 6 per parameter (each weight is one multiply-add
    forward, two backward; the tied embedding counts once, as the output
    head, the lookup is free), plus causal attention: the scores and the
    weighted values take 2 * 2 * d FLOP per (query, key) pair forward, a
    query sees (seq_len + 1) / 2 keys on average, and backward costs
    twice forward."""
    d, L = model["d_model"], model["n_layers"]
    hd = model.get("head_dim") or d // model["n_heads"]
    attn = 3 * 2 * 2 * model["n_heads"] * hd * L * (seq_len + 1) / 2
    return 6.0 * param_count(model) + attn


def hlo_bytes(instruction: str) -> float:
    """Bytes of an HLO instruction's result and operands, read from the
    shapes in its text (`%k = (f32[8,256], ...) custom-call(f32[8,256] %a,
    ...), custom_call_target=...`): each result written once, each
    operand read once. Attributes after the operand list are ignored."""
    text = instruction.split(", custom_call_target=")[0]
    text = text.split(", calls=")[0]
    total = 0.0
    for dtype, dims in _SHAPE.findall(text):
        n = 1
        for x in dims.split(","):
            if x:
                n *= int(x)
        total += n * ITEMSIZE[dtype]
    return total


def roofline_share(byte_counts: Sequence[float], flop_counts: Sequence[float],
                   times_s: Sequence[float], peaks: Dict[str, float]) -> float:
    """The least time the chip could take for the calls, the larger of
    operations over peak FLOP/s and bytes over peak bytes/s, over the
    time they took, in percent."""
    t_min = max(sum(flop_counts) / peaks["bf16_flops_per_s"],
                sum(byte_counts) / peaks["hbm_bytes_per_s"])
    return 100.0 * t_min / sum(times_s)
