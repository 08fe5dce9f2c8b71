"""DeepSeek-V2-style latent attention and routed experts as published, on the
served path: against the plain reference (``bench/reference/mla_moe.py``),
the held share of an expert layer, YaRN rotary, the router, the leading
dense layer, the dropless grouped expert path and the prefill's cache write.

All at small sizes on the CPU in float32, where program and reference agree
to rounding: the tolerances below are for summation order, not precision.
"""

from __future__ import annotations

import dataclasses
import json
import math
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.apps.common import group_layers, load_module, model_config
from repro.configs import get_config, get_smoke_config
from repro.models import moe as moe_mod
from repro.models import transformer as tx
from repro.models.common import YarnConfig
from repro.models.layers import (
    apply_rope,
    rope_frequencies,
    yarn_rotary_scale,
    yarn_softmax_factor,
)

ROOT = Path(__file__).resolve().parents[1]
CONFIG = ROOT / "bench" / "configs" / "deepseek-v2-lite.json"
YARN = {"factor": 40.0, "original_max_position": 4096, "beta_fast": 32.0, "beta_slow": 1.0,
        "mscale": 0.707, "mscale_all_dim": 0.707}
# the published block at small widths: 16 experts of which this share holds
# 4 to 7, top 4 unrenormalised, 2 shared, one leading dense layer
TINY = {"name": "tiny-dsv2", "family": "moe", "num_layers": 3, "d_model": 64, "num_heads": 4,
        "num_kv_heads": 4, "head_dim": 16, "d_ff": 96, "vocab_size": 256,
        "rope_theta": 10000.0,
        "mla": {"kv_lora_rank": 32, "qk_rope_dim": 8, "qk_nope_dim": 16, "v_head_dim": 16},
        "yarn": dict(YARN, original_max_position=64),
        "moe": {"num_experts": 16, "top_k": 4, "num_shared": 2, "expert_d_ff": 32,
                "first_dense": 1, "norm_topk_prob": False, "first_held": 4, "num_held": 4},
        "attention_chunk": 8, "param_dtype": "float32", "compute_dtype": "float32"}
KEY = jax.random.PRNGKey(16)


def _reference(model):
    mod = load_module(ROOT / "bench" / "reference" / "mla_moe.py", "test_reference_mla_moe")
    return mod.Reference(model, weight_dtype=jnp.float32, precision="reference")


def _params(model, key=KEY):
    cfg = model_config(model)
    shapes = jax.eval_shape(lambda k: tx.init_params(cfg, k), jax.random.PRNGKey(0))
    return cfg, weights.program_params(key, shapes, group_layers(cfg), jnp.float32)


def test_prefill_then_decode_match_the_reference():
    """The served calls (prefill into an empty latent cache, then decode
    steps through it) give the logits of the reference's full forward pass
    at every served position."""
    cfg, params = _params(TINY)
    B, PL, G = 2, 12, 5
    toks = np.random.default_rng(16).integers(0, TINY["vocab_size"], (B, PL + G)).astype(np.int32)
    ctx = tx.RunCtx(decode=True)
    cache = tx.init_cache(cfg, B, PL + G + 1)
    logits, cache = tx.prefill(cfg, params, jnp.asarray(toks[:, :PL]), cache, ctx)
    got = [logits[:, 0]]
    for i in range(G - 1):
        logits, cache = tx.decode_step(cfg, params, cache, jnp.asarray(toks[:, PL + i:PL + i + 1]),
                                       jnp.full((B, 1), PL + i, jnp.int32), ctx)
        got.append(logits[:, 0])
    want = _reference(TINY).forward(KEY, toks[:, :PL + G - 1], slice(PL - 1, PL + G - 1))
    # both in float32; the reference's products at HIGHEST precision
    np.testing.assert_allclose(np.stack(got, 1), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_positions_added_after_a_prefix_are_tiled_and_match_the_reference():
    """Several positions added to a cache that already holds a prefix (the
    absorbed core with more than one new position, as a prefill under a mesh
    takes) give the reference's logits, and the compiled call tiles its
    scores: no float32 buffer spans every new position and every slot."""
    cfg, params = _params(TINY)
    B, P, S = 2, 5, 12
    T = P + S + 3
    toks = np.random.default_rng(17).integers(0, TINY["vocab_size"], (B, P + S)).astype(np.int32)
    ctx = tx.RunCtx(decode=True)
    _, cache = tx.prefill(cfg, params, jnp.asarray(toks[:, :P]), tx.init_cache(cfg, B, T), ctx)
    positions = jnp.broadcast_to(P + jnp.arange(S)[None], (B, S))

    def extend(p, t, c):
        x, _, _ = tx.forward(cfg, p, t, positions=positions, cache=c, ctx=ctx)
        return tx.logits_matmul(cfg, p["embedding"], x)

    got = extend(params, jnp.asarray(toks[:, P:]), cache)
    want = _reference(TINY).forward(KEY, toks, slice(P, P + S))
    # both in float32; the reference's products at HIGHEST precision
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)
    text = jax.jit(extend).lower(params, jnp.asarray(toks[:, P:]), cache).compile().as_text()
    H = TINY["num_heads"]
    assert TINY["attention_chunk"] < S
    assert not re.search(rf"f32\[{B},{S},{H},{T}\]", text)


def test_shares_add_up_to_the_uncut_layer():
    """The 8 shares of a 64-expert layer (8 experts each), with the shared
    experts every share computes counted once, add up to the reference's
    uncut layer."""
    E, held, d = 64, 8, 32
    moe = {"num_experts": E, "top_k": 6, "num_shared": 2, "expert_d_ff": 16, "first_dense": 0,
           "norm_topk_prob": False}
    uncut = dict(TINY, d_model=d, num_layers=1, moe=moe)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8, d), jnp.float32)
    whole = {n: weights.leaf(KEY, f"moe/{n}", 0, s, jnp.float32) for n, s in {
        "router": (d, E), "w_gate": (E, d, 16), "w_up": (E, d, 16), "w_down": (E, 16, d),
        "shared/w_gate": (d, 32), "shared/w_up": (d, 32), "shared/w_down": (32, d)}.items()}
    shared = {k.split("/")[1]: v for k, v in whole.items() if k.startswith("shared/")}
    total = 0.0
    for s in range(E // held):
        cfg = model_config(dict(uncut, moe=dict(moe, first_held=s * held, num_held=held)))
        p = {"router": whole["router"], "shared": shared,
             **{n: whole[n][s * held:(s + 1) * held] for n in ("w_gate", "w_up", "w_down")}}
        y, _ = moe_mod.apply_moe(cfg, p, x, serving=True)
        total = total + y
    cfg = model_config(uncut)
    once = moe_mod._shared_ffn(cfg, {"shared": shared}, x.reshape(-1, d)).reshape(x.shape)
    want = _reference(uncut).moe(KEY, x, jnp.int32(0))
    # float32 sums of the same terms in another order
    np.testing.assert_allclose(total - (E // held - 1) * once, want, rtol=1e-5, atol=1e-5)


def test_yarn_frequencies_and_mscale():
    """DeepSeek-V2-Lite's rotary (64 dims, theta 1e4, factor 40 over 4096
    positions, beta 32 and 1): pairs 0-10 keep 10^(-i/8), pairs 23-31 take
    it over 40, and the ramp between runs over (i - 10) / 13."""
    yarn = YarnConfig(**YARN)
    f = rope_frequencies(64, 10000.0, yarn)
    table = {0: 1.0, 10: 0.056234132519034905, 11: 0.03900692656714386, 16: 0.0055,
             22: 0.0001778279410038922, 23: 3.33380358040831e-05, 31: 3.3338035804083097e-06}
    for i, want in table.items():
        assert f[i] == pytest.approx(want, rel=1e-12)
    # (0.1 * 0.707 * ln 40 + 1)^2 on the softmax scale; cos/sin unscaled
    assert yarn_softmax_factor(yarn) == pytest.approx(1.5896261651208736, rel=1e-12)
    assert yarn_rotary_scale(yarn) == 1.0
    assert yarn_softmax_factor(None) == yarn_rotary_scale(None) == 1.0
    lifted = YarnConfig(**dict(YARN, mscale=1.0, mscale_all_dim=0.0))
    assert yarn_rotary_scale(lifted) == pytest.approx(0.1 * math.log(40) + 1, rel=1e-12)


def test_rope_without_scaling_is_unchanged():
    """The rotary of every other model, bit for bit what it was."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 7, 3, 16), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(7)[None], (2, 7)) + 500
    freqs = jnp.asarray(1.0 / (10000.0 ** (np.arange(0, 16, 2) / 16)), jnp.float32)
    ang = pos[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    want = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    np.testing.assert_array_equal(apply_rope(x, pos, 10000.0), want)


@pytest.mark.parametrize("renormalise", [False, True])
def test_router_weights(renormalise):
    """Without ``norm_topk_prob`` the top-k weights are the softmax scores
    themselves; with it they sum to one."""
    cfg = model_config(dict(TINY, moe=dict(TINY["moe"], norm_topk_prob=renormalise)))
    x = jax.random.normal(jax.random.PRNGKey(2), (10, 64), jnp.float32)
    router = jax.random.normal(jax.random.PRNGKey(4), (64, 16), jnp.float32)
    probs, top_i, top_w = moe_mod._router(cfg, {"router": router}, x)
    raw = jnp.take_along_axis(jax.nn.softmax(x @ router, axis=-1), top_i, axis=-1)
    if renormalise:
        np.testing.assert_allclose(top_w.sum(-1), 1.0, rtol=1e-6)
        np.testing.assert_allclose(top_w, raw / raw.sum(-1, keepdims=True), rtol=1e-5)
    else:
        np.testing.assert_array_equal(top_w, jnp.take_along_axis(probs, top_i, axis=-1))
        np.testing.assert_allclose(top_w, raw, rtol=1e-5)
        assert float(top_w.sum(-1).max()) < 1.0


@pytest.mark.parametrize("arch,width", [("deepseek-v2-lite-16b", 10944),
                                        ("kimi-k2-1t-a32b", 18432)])
def test_leading_dense_layer_is_d_ff_wide(arch, width):
    cfg = get_config(arch)
    assert cfg.d_ff == width
    shapes = jax.eval_shape(lambda k: tx.init_params(cfg, k), jax.random.PRNGKey(0))
    assert shapes["dense0"]["mlp"]["w_gate"].shape == (1, cfg.d_model, width)
    assert shapes["moe"]["moe"]["w_gate"].shape[-1] == cfg.moe.expert_d_ff


def test_expert_blocks_follow_the_routed_pairs():
    """Every routed (token, held expert) pair has exactly one row, sorted by
    expert; each expert's rows are padded to at most one block; pairs on
    experts held elsewhere have none."""
    N, k, E, held = 300, 6, 64, 8
    top_i = jax.vmap(lambda key: jax.random.permutation(key, E)[:k])(
        jax.random.split(jax.random.PRNGKey(5), N))
    cfg = model_config(dict(TINY, moe=dict(TINY["moe"], num_experts=E, top_k=k,
                                           first_held=16, num_held=held)))
    local = moe_mod._held_index(cfg, top_i).reshape(-1)
    rows = moe_mod.block_rows(N, cfg)
    block_expert, pairs, n_blocks = moe_mod.expert_blocks(local, held, N, k, rows)
    local, pairs, n = np.asarray(local), np.asarray(pairs), int(n_blocks)
    routed = int((local < held).sum())
    used = pairs[:n]
    real = used[used < N * k]
    assert sorted(real) == sorted(np.flatnonzero(local < held))
    assert (pairs[n:] == N * k).all()
    assert routed <= n * rows < routed + held * rows
    for b in range(n):
        got = local[used[b][used[b] < N * k]]
        assert (got == np.asarray(block_expert)[b]).all()


def _compiled_prefill(model, B=2, S=64):
    cfg = model_config(model)
    params = jax.eval_shape(lambda k: tx.init_params(cfg, k), jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: tx.init_cache(cfg, B, S + 4))
    toks = jax.ShapeDtypeStruct((B, S), jnp.int32)
    ctx = tx.RunCtx(decode=True)
    return jax.jit(lambda p, t, c: tx.prefill(cfg, p, t, c, ctx)).lower(
        params, toks, cache).compile().as_text()


def test_served_prefill_computes_routed_rows_only():
    """The compiled prefill's routed-expert products have a block of rows
    each, run as many times as there are blocks in use, and no product
    spans every token for every held expert."""
    B, S = 2, 64
    model = dict(TINY, moe=dict(TINY["moe"], num_held=8, first_held=0))
    cfg = model_config(model)
    rows = moe_mod.block_rows(B * S, cfg)
    text = _compiled_prefill(model, B, S)
    d, f, held = cfg.d_model, cfg.moe.expert_d_ff, cfg.moe.held
    shapes = re.findall(r"= f32\[([\d,]+)\][^ ]* (?:dot|convolution)\(", text)
    assert f"{rows},{f}" in shapes and f"{rows},{d}" in shapes
    assert not any(s.startswith(f"{held},{B * S}") or s.startswith(f"{held},{B},{S}")
                   for s in shapes)
    assert rows < B * S * cfg.moe.top_k


def test_served_prefill_writes_the_latent_cache_in_one_slice():
    """A prefill into an empty cache attends over its own keys: a cache of
    NaN changes nothing it returns, and its latent ``c`` (normed) and
    rotary keys land in slots 0..S-1 by one slice, the rest untouched."""
    model = dict(TINY, family="dense", moe=None)
    del model["moe"]
    cfg, params = _params(model)
    B, S = 2, 10
    toks = jnp.asarray(np.random.default_rng(6).integers(0, 256, (B, S)).astype(np.int32))
    empty = tx.init_cache(cfg, B, S + 6)
    poisoned = jax.tree.map(lambda c: jnp.full_like(c, jnp.nan) if c.dtype == jnp.float32
                            else c, empty)
    jaxpr = str(jax.make_jaxpr(lambda p, t, c: tx.prefill(cfg, p, t, c))(params, toks, empty))
    assert "dynamic_update_slice" in jaxpr and not re.search(r"\bscatter\[", jaxpr)
    clean, c1 = tx.prefill(cfg, params, toks, empty)
    dirty, c2 = tx.prefill(cfg, params, toks, poisoned)
    np.testing.assert_array_equal(clean, dirty)
    # the general cache path writes the same values by indexing (float32
    # projections of order-one values, fused otherwise)
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    _, indexed, _ = tx.forward(cfg, params, toks, positions=positions, cache=empty)
    for name in ("c", "k_rope"):
        got, want = np.asarray(c2["layers"][name]), np.asarray(indexed["layers"][name])
        np.testing.assert_allclose(got[:, :, :S], want[:, :, :S], rtol=1e-5, atol=1e-5)
        assert np.isnan(got[:, :, S:]).all()
    np.testing.assert_array_equal(c2["layers"]["length"], S)
    # the cache holds the normed latent: unit RMS under unit scales
    rms = np.sqrt((np.asarray(c1["layers"]["c"])[:, :, :S] ** 2).mean(-1))
    np.testing.assert_allclose(rms, 1.0, rtol=1e-3)


# fields that set how the program runs, not what the model is
EXECUTION = {"name", "param_dtype", "compute_dtype", "attention_impl", "attention_chunk",
             "aligned_decode", "scan_layers", "moe_impl", "remat", "num_microbatches",
             "logits_chunk", "max_seq_len"}
# the configuration file's published keys, as the program's fields
PUBLISHED = {"hidden_size": "d_model", "intermediate_size": "d_ff",
             "num_hidden_layers": "num_layers", "num_attention_heads": "num_heads",
             "num_key_value_heads": "num_kv_heads", "vocab_size": "vocab_size",
             "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
             "kv_lora_rank": "mla.kv_lora_rank", "qk_rope_head_dim": "mla.qk_rope_dim",
             "qk_nope_head_dim": "mla.qk_nope_dim", "v_head_dim": "mla.v_head_dim",
             "moe_intermediate_size": "moe.expert_d_ff", "num_experts_per_tok": "moe.top_k",
             "n_shared_experts": "moe.num_shared", "first_k_dense_replace": "moe.first_dense",
             "norm_topk_prob": "moe.norm_topk_prob",
             "routed_scaling_factor": "moe.routed_scaling_factor",
             "n_routed_experts": "moe.num_held"}


def _field(cfg, path):
    for part in path.split("."):
        cfg = getattr(cfg, part)
    return cfg


def test_benchmark_configuration_is_the_registrys():
    """The benchmark's ``model`` section is the registry's configuration
    apart from the keys its file lists in ``reduced`` (the held experts) and
    how the program runs; its published keys are the section's fields."""
    conf = json.loads(CONFIG.read_text())
    bench, registry = model_config(conf["model"]), get_config("deepseek-v2-lite-16b")
    assert conf["reduced"] == ["n_routed_experts"]
    assert (bench.moe.first_held, bench.moe.held) == (0, conf["n_routed_experts"]) == (0, 8)
    assert registry.moe.held == conf["published"]["n_routed_experts"] == 64
    cut = dataclasses.replace(registry.moe, first_held=0, num_held=8)
    for f in dataclasses.fields(registry):
        if f.name not in EXECUTION:
            want = cut if f.name == "moe" else getattr(registry, f.name)
            assert getattr(bench, f.name) == want, f.name
    for key, path in PUBLISHED.items():
        assert _field(bench, path) == conf[key], key
        if key not in conf["reduced"]:
            assert conf[key] == conf["published"][key], key
    rs = conf["rope_scaling"]
    assert bench.yarn == YarnConfig(rs["factor"], rs["original_max_position_embeddings"],
                                    rs["beta_fast"], rs["beta_slow"], rs["mscale"],
                                    rs["mscale_all_dim"])
    assert conf["q_lora_rank"] is None and bench.mla.q_lora_rank == 0
    assert conf["rms_norm_eps"] == 1e-6 and conf["scoring_func"] == "softmax"


def test_smoke_config_runs_the_published_block():
    cfg = get_smoke_config("deepseek-v2-lite-16b")
    assert cfg.yarn is not None and not cfg.moe.norm_topk_prob
    shapes = jax.eval_shape(lambda k: tx.init_params(cfg, k), jax.random.PRNGKey(0))
    assert shapes["moe"]["attn"]["kv_norm"]["scale"].shape == (2, cfg.mla.kv_lora_rank)
