"""A run of a cell at a size the CPU holds, with the chip check skipped."""

from __future__ import annotations

import time

import jax

from bench import run

TINY = {"name": "tiny", "family": "dense", "num_layers": 2, "d_model": 64, "num_heads": 4,
        "num_kv_heads": 2, "head_dim": 16, "d_ff": 128, "vocab_size": 256,
        "rope_theta": 10000.0, "tie_embeddings": True,
        "param_dtype": "float32", "compute_dtype": "float32"}
PEAKS = {"flops_per_s_bf16": 1e12, "hbm_bytes_per_s": 1e11}
# latent attention and routed experts at the widths of
# repro.configs.deepseek_v2_lite_16b.smoke_config, one leading dense layer
TINY_MLA_MOE = {"name": "tiny-mla-moe", "family": "moe", "num_layers": 3, "d_model": 64,
                "num_heads": 4, "num_kv_heads": 4, "head_dim": 16, "d_ff": 64,
                "vocab_size": 256, "rope_theta": 10000.0,
                "mla": {"kv_lora_rank": 32, "qk_rope_dim": 8, "qk_nope_dim": 16,
                        "v_head_dim": 16},
                "moe": {"num_experts": 8, "top_k": 2, "num_shared": 1, "expert_d_ff": 64,
                        "first_dense": 1},
                "moe_impl": "dense", "param_dtype": "float32", "compute_dtype": "float32"}


def chat_mix(**kw):
    mix = dict(run.load_json(run.BENCH / "traffic" / "chat.json"), prompt_len=16,
               new_tokens=8, rate_per_s=12.0, max_batch_size=4, check_requests=3,
               trace_last_s=1.5)
    mix.update(kw)
    return mix


def run_serve(seed=2**33 + 5, seconds=2.0, trace=False, model=None, mix=None,
              limits=None, spec=None, cell="serve.phi4.chat"):
    spec = spec or run.load_json(run.ROOT / "BENCHMARK.json")
    return run.execute(spec, cell, seed, seconds, trace, devices=jax.devices()[:1],
                       peaks=PEAKS, model=model or TINY, mix=mix or chat_mix(),
                       limits=limits or {"mean_logit_gap": 1e-4}, t_process=time.perf_counter(),
                       log=lambda *a: None)


TINY_HYBRID = {"name": "tiny-hybrid", "family": "hybrid", "num_layers": 4, "d_model": 64,
               "num_heads": 4, "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
               "vocab_size": 256, "rope_theta": 10000.0, "sliding_window": 16,
               "global_layers": [0, 3],
               "ssm": {"d_state": 8, "d_conv": 4, "expand": 1, "head_dim": 16, "chunk": 16},
               "param_dtype": "float32", "compute_dtype": "float32"}


def train_mix(**kw):
    mix = dict(run.load_json(run.BENCH / "traffic" / "pretrain2k.json"), batch=4, seq=64,
               trace_last_s=1.5)
    mix.update(kw)
    return mix


def train_spec():
    """``BENCHMARK.json`` with the training cell, whether or not it is listed."""
    spec = run.load_json(run.ROOT / "BENCHMARK.json")
    if not any(w["name"] == "train.hymba.fsdp4" for w in spec["workloads"]):
        spec["configs"].append({"name": "hymba-1.5b", "source": "-",
                                "file": "bench/configs/hymba-1.5b.json", "reduced": [],
                                "why": "-"})
        spec["workloads"].append({"name": "train.hymba.fsdp4", "config": "hymba-1.5b",
                                  "traffic": "pretrain2k", "chips": 4, "why": "-"})
        spec["end_to_end"].append({"name": "train_tok_s", "unit": "tokens/s",
                                   "better": "higher", "bound": 0.05,
                                   "source": "host_clock",
                                   "workloads": ["train.hymba.fsdp4"]})
    return spec
