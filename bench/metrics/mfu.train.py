"""mfu.train: model FLOP/s of the training window as a share of the chips'
bf16 peak. FLOP per token from bench/counts.py (6 per parameter plus causal
attention, no recomputation), times the tokens the window consumed, over
the window and chips x peak."""


def read(r):
    j = r.job["readings"]
    return 100.0 * j["flops_per_token"] * j["tokens"] / j["window_s"] / (
        j["chips"] * r.peaks["bf16_flops_per_s"])
