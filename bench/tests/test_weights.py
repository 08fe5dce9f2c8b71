"""Weights drawn from the seed: the bits of every leaf the harness drew
before latent attention and routed experts had rules are pinned, and the
new rules take each matrix's fan-in from the right axis."""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.apps.common import group_layers, model_config
from bench.generate import jax_key
from bench.tests.helpers import TINY, TINY_HYBRID

SEED = 2**33 + 17

# crc32 of each leaf's bytes under SEED
TINY_CRC = {
    "embedding/embed": 1030615436,
    "final_norm/scale": 3062745768,
    "layers/attn/w_k": 233817550,
    "layers/attn/w_o": 3782691873,
    "layers/attn/w_q": 564310275,
    "layers/attn/w_v": 1705125166,
    "layers/ln1/scale": 851035124,
    "layers/ln2/scale": 851035124,
    "layers/mlp/w_down": 3191514264,
    "layers/mlp/w_gate": 358910281,
    "layers/mlp/w_up": 1882094849,
}
TINY_HYBRID_CRC = {
    "embedding/embed": 1030615436,
    "embedding/unembed": 4268387636,
    "final_norm/scale": 3062745768,
    "global0/attn/w_k": 625874722,
    "global0/attn/w_o": 2253972637,
    "global0/attn/w_q": 853346863,
    "global0/attn/w_v": 4136964636,
    "global0/beta_attn": 3062745768,
    "global0/beta_ssm": 3062745768,
    "global0/ln1/scale": 3062745768,
    "global0/ln2/scale": 3062745768,
    "global0/mamba/a_log": 1750766016,
    "global0/mamba/conv_b": 2915522381,
    "global0/mamba/conv_w": 2198241837,
    "global0/mamba/d_skip": 3899135062,
    "global0/mamba/dt_bias": 386651428,
    "global0/mamba/norm_scale": 3062745768,
    "global0/mamba/w_in": 2479294709,
    "global0/mamba/w_out": 3907448888,
    "global0/mlp/w_down": 3260810286,
    "global0/mlp/w_gate": 1620707379,
    "global0/mlp/w_up": 3166818615,
    "global1/attn/w_k": 2181477800,
    "global1/attn/w_o": 1355239480,
    "global1/attn/w_q": 71867815,
    "global1/attn/w_v": 2036993486,
    "global1/beta_attn": 3062745768,
    "global1/beta_ssm": 3062745768,
    "global1/ln1/scale": 3062745768,
    "global1/ln2/scale": 3062745768,
    "global1/mamba/a_log": 1750766016,
    "global1/mamba/conv_b": 2915522381,
    "global1/mamba/conv_w": 2769450922,
    "global1/mamba/d_skip": 3899135062,
    "global1/mamba/dt_bias": 386651428,
    "global1/mamba/norm_scale": 3062745768,
    "global1/mamba/w_in": 1243919067,
    "global1/mamba/w_out": 1129762215,
    "global1/mlp/w_down": 1901038516,
    "global1/mlp/w_gate": 906853191,
    "global1/mlp/w_up": 1707168908,
    "local1/attn/w_k": 307845301,
    "local1/attn/w_o": 1318043616,
    "local1/attn/w_q": 1801099143,
    "local1/attn/w_v": 4070315303,
    "local1/beta_attn": 851035124,
    "local1/beta_ssm": 851035124,
    "local1/ln1/scale": 851035124,
    "local1/ln2/scale": 851035124,
    "local1/mamba/a_log": 134948583,
    "local1/mamba/conv_b": 1266095834,
    "local1/mamba/conv_w": 3236260212,
    "local1/mamba/d_skip": 4137743041,
    "local1/mamba/dt_bias": 2714817459,
    "local1/mamba/norm_scale": 851035124,
    "local1/mamba/w_in": 1236665989,
    "local1/mamba/w_out": 1375687183,
    "local1/mlp/w_down": 2120535649,
    "local1/mlp/w_gate": 1535828255,
    "local1/mlp/w_up": 562022371,
}


def drawn(m, seed=SEED):
    from repro.models import transformer as tx

    cfg = model_config(m)
    shapes = jax.eval_shape(lambda k: tx.init_params(cfg, k), jax.random.PRNGKey(0))
    tree = jax.jit(lambda k: weights.program_params(k, shapes, group_layers(cfg),
                                                    cfg.param_dtype))(jax_key(seed))
    return {"/".join(str(getattr(k, "key", k)) for k in path): np.asarray(x)
            for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.mark.parametrize("model,crc", [(TINY, TINY_CRC), (TINY_HYBRID, TINY_HYBRID_CRC)],
                         ids=["dense", "hybrid"])
def test_existing_leaves_keep_their_bits(model, crc):
    assert {k: zlib.crc32(x.tobytes()) for k, x in drawn(model).items()} == crc


def test_fan_in_of_latent_attention_and_experts():
    """Entries are uniform on +-std*sqrt(3): the largest of many lies just
    under that bound, which pins each leaf's std to its fan-in axis."""
    E, d, f, r, H = 4, 32, 96, 24, 2
    shapes = {"attn": {"w_dkv": (d, r + 8), "w_uk": (r, H, 16), "w_uv": (r, H, 16)},
              "moe": {"router": (d, E), "w_gate": (E, d, f), "w_up": (E, d, f),
                      "w_down": (E, f, d),
                      "shared": {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}},
              "kv_norm": {"scale": (r,)}}
    fan_in = {"attn/w_dkv": d, "attn/w_uk": r, "attn/w_uv": r, "moe/router": d,
              "moe/w_gate": d, "moe/w_up": d, "moe/w_down": f, "moe/shared/w_gate": d,
              "moe/shared/w_up": d, "moe/shared/w_down": f}
    tree = {"layers": jax.tree.map(lambda s: jax.ShapeDtypeStruct((2, *s), jnp.float32), shapes,
                                   is_leaf=lambda s: isinstance(s, tuple))}
    out = jax.jit(lambda k: weights.program_params(k, tree, {"layers": [3, 4]}, jnp.float32))(
        jax_key(SEED))
    leaves = {"/".join(str(k.key) for k in path[1:]): np.asarray(x)
              for path, x in jax.tree_util.tree_flatten_with_path(out)[0]}
    for name, n in fan_in.items():
        top = np.abs(leaves[name]).max() / (n ** -0.5 * 3 ** 0.5)
        assert 0.97 < top <= 1.0, (name, top)
    assert np.all(leaves["kv_norm/scale"] == 1.0)
