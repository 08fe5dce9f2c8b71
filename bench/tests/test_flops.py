"""FLOP and byte counts against hand counts at smoke sizes, parameter
counts against the program's parameter tree, and the counts of the
configurations in use pinned."""

import pytest

from bench import flops, run
from bench.tests.helpers import TINY_MLA_MOE

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


# latent attention (r 6, rope 2, nope 4, v 4) and routed experts (4 of width
# 4, top 2, one shared), one leading dense layer of width 16
MLA_MOE = dict(TINY, mla={"kv_lora_rank": 6, "qk_rope_dim": 2, "qk_nope_dim": 4,
                          "v_head_dim": 4},
               moe={"num_experts": 4, "top_k": 2, "num_shared": 1, "expert_d_ff": 4,
                    "first_dense": 1})


def test_mla_and_routed_layers_by_hand():
    # attn: q 8*2*(4+2)=96, down 8*(6+2)=64, up 6*2*(4+4)=96, o 2*4*8=64 -> 320
    assert flops.attn_params(MLA_MOE) == 320
    # leading dense layer: 320 + 3*8*16 = 704, per token and stored
    assert flops.layer_params(MLA_MOE, 0) == (704, 704)
    # routed: expert 3*8*4=96, router 8*4=32; per token 2 routed + 1 shared,
    # stored all 4 + 1 shared
    assert flops.layer_params(MLA_MOE, 1) == (320 + 3 * 96 + 32, 320 + 5 * 96 + 32)
    assert flops.matmul_params(MLA_MOE) == {"layer": 640, "layers": 1344, "head": 80}
    # + 2 norms of 8 per layer + tied embedding 80 + final norm 8
    assert flops.param_count(MLA_MOE) == 704 + 832 + 2 * 16 + 80 + 8


def test_mla_prefill_expanded_by_hand():
    # 1 row of 3 tokens: matmuls 2*1344*3, logits 2*80; per pair expanded
    # 2*H*(nope+rope) + 2*H*v = 24 + 16 = 40, over 1+2+3 pairs in 2 layers
    assert flops.prefill_flops(MLA_MOE, 1, 3) == 2 * 1344 * 3 + 2 * 80 + 40 * 6 * 2


def test_mla_decode_absorbed_by_hand():
    # position 4 sees 5 keys: per pair absorbed 2*H*(r+rope) + 2*H*r = 32 + 24
    assert flops.decode_flops(MLA_MOE, 1, 4) == 2 * (1344 + 80) + 56 * 5 * 2
    # bytes: every parameter once but the routed layer's 4 experts, of which
    # 3 were read; the latent cache: (r + rope) = 8 values a position a layer
    weights = (1656 - 4 * 96 + 3 * 96) * 2
    assert flops.decode_bytes(MLA_MOE, 1, 4, experts_read=3) == weights + 5 * 8 * 2 * 2
    with pytest.raises(ValueError):
        flops.decode_bytes(MLA_MOE, 1, 4)


def test_mla_train_by_hand():
    # 6 per multiply weight; expanded attention 3x forward over a mean 2.5 keys
    assert flops.train_flops_per_token(MLA_MOE, 4) == 6 * (1344 + 80) + 2 * 3 * 40 * 2.5


def _program_param_count(m):
    import math

    import jax

    from bench.apps.common import model_config
    from repro.models import transformer as tx

    cfg = model_config(m)
    shapes = jax.eval_shape(lambda k: tx.init_params(cfg, k), jax.random.PRNGKey(0))
    return sum(math.prod(s.shape) for s in jax.tree.leaves(shapes))


def _config(name):
    return run.load_json(run.BENCH / "configs" / f"{name}.json")["model"]


@pytest.mark.parametrize("name,count", [("phi4-mini-3.8b", 3_836_021_760),
                                        ("hymba-1.5b", 1_393_625_120)])
def test_param_count_is_the_programs(name, count):
    m = _config(name)
    assert flops.param_count(m) == _program_param_count(m) == count


@pytest.mark.parametrize("shared", [1, 0])
def test_param_count_is_the_programs_mla_moe(shared):
    # no leading dense layer: the program's is (top_k + shared) * expert_d_ff
    # wide where the configuration states d_ff
    m = dict(TINY_MLA_MOE, moe=dict(TINY_MLA_MOE["moe"], first_dense=0, num_shared=shared))
    assert flops.param_count(m) == _program_param_count(m)


def test_gqa_moe_param_count_is_the_programs():
    m = dict(TINY_MLA_MOE, moe=dict(TINY_MLA_MOE["moe"], first_dense=0), num_kv_heads=2)
    del m["mla"]
    assert flops.param_count(m) == _program_param_count(m)


def test_dense_and_hybrid_counts_pinned():
    """The counts the serve cell's readers and the training cell take, as
    they were before latent attention and experts were counted."""
    phi, hy = _config("phi4-mini-3.8b"), _config("hymba-1.5b")
    positions = range(512, 639)
    assert flops.matmul_params(phi) == {"layer": 100663296, "layers": 3221225472,
                                        "head": 614596608}
    assert flops.prefill_flops(phi, 16, 512) == 53622469558272.0
    assert flops.decode_flops(phi, 16, 512) == 125973823488.0
    assert flops.decode_flops(phi, 16, 638) == 126766546944.0
    assert sum(flops.decode_flops(phi, 16, p) for p in positions) / 127 == 126370185216.0
    assert flops.decode_bytes(phi, 16, 512) == 8747882496.0
    assert flops.decode_bytes(phi, 16, 638) == 9012123648.0
    assert sum(flops.decode_bytes(phi, 16, p) for p in positions) / 127 == 8880003072.0
    assert flops.matmul_params(hy) == {"layer": 40334400, "layers": 1290700800,
                                       "head": 51201600}
    assert flops.train_flops_per_token(hy, 2048) == 8538187200.0
    assert flops.prefill_flops(hy, 4, 2048) == 22476465779200.0
    assert flops.decode_flops(hy, 4, 1500) == 11610713600.0
    assert flops.decode_bytes(hy, 4, 1500, 4, 2) == 5749599360.0
