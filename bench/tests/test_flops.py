"""FLOP and byte counts against hand counts at smoke sizes."""

import pytest

from bench import flops

TINY = {"num_layers": 2, "d_model": 8, "num_heads": 2, "num_kv_heads": 1, "head_dim": 4,
        "d_ff": 16, "vocab_size": 10, "tie_embeddings": True}


def test_matmul_params_by_hand():
    # attn: q 8*2*4=64, k 8*1*4=32, v 32, o 2*4*8=64 -> 192; mlp 3*8*16=384
    mp = flops.matmul_params(TINY)
    assert mp == {"layer": 576, "layers": 1152, "head": 80}
    # + 2 norms of 8 per layer + tied embedding 80 + final norm 8
    assert flops.param_count(TINY) == 1152 + 2 * 16 + 80 + 8


def test_prefill_by_hand():
    # 2 rows of 3 tokens: matmuls 2*1152*6, logits of the last position 2*80*2,
    # attention per layer 4*H*hd*(1+2+3) per row = 4*2*4*6 = 192, x2 rows x2 layers
    assert flops.prefill_flops(TINY, 2, 3) == 2 * 1152 * 6 + 2 * 80 * 2 + 192 * 2 * 2


def test_decode_by_hand():
    # position 4 sees 5 keys: per row 2*(1152+80) + 2 layers * 4*2*4*5
    assert flops.decode_flops(TINY, 3, 4) == 3 * (2 * 1232 + 2 * 160)
    # bytes: every parameter once in bf16, keys+values of 5 positions x 2 layers
    kv = 3 * 5 * 2 * 1 * 4 * 2 * 2
    assert flops.decode_bytes(TINY, 3, 4) == flops.param_count(TINY) * 2 + kv


def test_window_caps_what_a_query_sees():
    m = dict(TINY, sliding_window=2, global_layers=[0])
    assert flops.layer_kinds(m) == [0, 2]
    # layer 0 sees 5 keys, layer 1 (window 2) sees 2
    assert flops.decode_flops(m, 1, 4) == 2 * 1232 + 32 * 5 + 32 * 2


def test_train_by_hand():
    # 6 per multiply weight; attention 3x forward, causal mean over seq 4 = 2.5 keys
    assert flops.train_flops_per_token(TINY, 4) == pytest.approx(6 * 1232 + 2 * 3 * 32 * 2.5)


def test_ssm_weights_counted():
    m = dict(TINY, ssm={"expand": 1, "head_dim": 4, "d_state": 2, "d_conv": 4})
    # in_proj d*(2*din + 2N + nh) = 8*(16+4+2) = 176, out_proj din*d = 64
    assert flops.matmul_params(m)["layer"] == 576 + 176 + 64
