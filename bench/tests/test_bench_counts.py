"""FLOP and byte counts against hand counts at a tiny size."""
import pytest

from bench import counts

TINY = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 2,
        "d_ff": 16, "vocab_size": 10, "norm": "layernorm", "gated_mlp": False,
        "tie_embeddings": True}


def test_param_count_by_hand():
    # embed 10*8; per layer q,k,v,o 4*8*8, mlp 2*8*16, two norms 2*(2*8);
    # final norm 2*8
    assert counts.param_count(TINY) == 80 + 2 * (256 + 256 + 32) + 16


def test_param_count_gated_nonparam():
    m = dict(TINY, gated_mlp=True, norm="nonparam_ln")
    assert counts.param_count(m) == 80 + 2 * (256 + 384)


def test_param_count_matches_the_program():
    from repro.configs import get_config
    for arch in ("transformer-wmt", "olmo-1b"):
        cfg = get_config(arch)
        m = {k: getattr(cfg, k) for k in (
            "n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff",
            "vocab_size", "norm", "gated_mlp", "tie_embeddings")}
        assert counts.param_count(m) == cfg.n_params()


def test_train_flops_per_token_by_hand():
    seq = 4
    n = counts.param_count(TINY)
    # causal: a query at position i sees i+1 keys, mean (4+1)/2 = 2.5;
    # per (query, key) pair 2 FLOP for the score and 2 for the weighted
    # value per head dim: 4 * d per pair per layer; times 3 for backward
    attn = 3 * 4 * 8 * 2.5 * 2
    assert counts.train_flops_per_token(TINY, seq) == pytest.approx(
        6 * n + attn)


def test_hlo_bytes_reads_result_and_operands():
    text = ('%sgd_update.1 = (f32[512,512]{1,0:T(8,128)S(1)}, '
            'f32[512,512]{1,0}) custom-call(f32[1]{0:T(128)} %c, '
            'f32[512,512]{1,0} %p, bf16[512,512]{1,0} %g, u8[16,256] %m), '
            'custom_call_target="tpu_custom_call", operand_layout_'
            'constraints={f32[1]{0}, f32[512,512]{1,0}}')
    want = 2 * 512 * 512 * 4 + 4 + 512 * 512 * 4 + 512 * 512 * 2 + 16 * 256
    assert counts.hlo_bytes(text) == want


def test_roofline_share_takes_the_binding_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    # 50 bytes -> 5 s at peak, 100 FLOP -> 1 s: bytes bind; took 10 s
    assert counts.roofline_share([20, 30], [100.0], [4, 6], peaks) == 50.0
    assert counts.roofline_share([1], [1000.0], [20], peaks) == 50.0
