"""The harness end to end on the CPU: the result line, finding files by name,
refusing a machine without a chip, and weights that match the reference."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from bench import run, weights
from bench.generate import jax_key
from bench.tests.helpers import TINY, TINY_MLA_MOE, chat_mix, run_serve

STUB_REFERENCE = '''
"""A reference that records its calls and returns zero logits."""
import json
from pathlib import Path

import jax.numpy as jnp

CALLS = Path(__file__).with_suffix(".calls")


def note(**kw):
    with open(CALLS, "a") as f:
        f.write(json.dumps(kw) + "\\n")


class Reference:
    def __init__(self, model, *, weight_dtype, precision):
        self.vocab = model["vocab_size"]
        note(call="init", model=model["name"], precision=precision)

    def forward(self, key, tokens, read):
        note(call="forward", rows=len(tokens))
        return jnp.zeros((*tokens[:, read].shape, self.vocab))
'''


def test_result_line_keys():
    line = run_serve()
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] == 24
    assert set(line["metrics"]) == {"setup_s", "req_p50_s", "req_p95_s"}
    assert all(set(m) == {"value", "unit"} for m in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert all(set(c) == {"value", "limit"} for c in line["checks"].values())
    json.dumps(line)


def test_traced_line_keys():
    line = run_serve(trace=True)
    assert list(line)[-1] == "checks"
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    # only per-layer metrics; the CPU trace has no TPU ops, so the trace
    # readers find nothing and leave their metrics out
    assert set(line["metrics"]) == {"queue_wait_ms.serve", "batch_fill.serve",
                                    "idle_share.serve"}


def test_new_traffic_file_found_by_name(tmp_path, monkeypatch):
    for sub in ("traffic", "limits", "metrics", "apps"):
        (tmp_path / sub).mkdir()
    (tmp_path / "traffic" / "tiny_burst.json").write_text(json.dumps(chat_mix(rate_per_s=6.0)))
    (tmp_path / "limits" / "serve.tiny.burst.json").write_text(json.dumps({"mean_logit_gap": 1e-4}))
    monkeypatch.setattr(run, "BENCH", tmp_path)
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "serve.tiny.burst", "config": spec["configs"][0]["name"],
                              "traffic": "tiny_burst", "chips": 1, "why": "test"})
    line = run.execute(spec, "serve.tiny.burst", 3, 2.0, False, devices=jax.devices()[:1],
                       peaks={}, model=TINY, log=lambda *a: None)
    assert line["attempted"] == 12 and line["correct"] is True
    # the e2e metric whose workloads name only the chat cell is not reported
    assert set(line["metrics"]) == {"setup_s"}


def test_new_config_found_by_name(tmp_path, monkeypatch):
    """A configuration of another architecture (latent attention, routed
    experts) enters by new files and entries alone: its file, its reference,
    a traffic mix and limits."""
    for sub in ("traffic", "limits", "configs", "reference"):
        (tmp_path / sub).mkdir()
    mix = chat_mix()
    (tmp_path / "traffic" / "tiny_chat.json").write_text(json.dumps(mix))
    (tmp_path / "limits" / "serve.tiny.mla.json").write_text(json.dumps({"mean_logit_gap": 1e-4}))
    stub = tmp_path / "reference" / "stub.py"
    stub.write_text(STUB_REFERENCE)
    conf = tmp_path / "configs" / "tiny-mla-moe.json"
    conf.write_text(json.dumps({"reference": str(stub), "model": TINY_MLA_MOE}))
    monkeypatch.setattr(run, "BENCH", tmp_path)
    drawn = set()
    leaf = weights.leaf

    def recorded(key, name, layer, shape, dtype):
        drawn.add(name)
        return leaf(key, name, layer, shape, dtype)

    monkeypatch.setattr(weights, "leaf", recorded)
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-mla-moe", "source": "-", "file": str(conf),
                            "reduced": [], "why": "-"})
    spec["workloads"].append({"name": "serve.tiny.mla", "config": "tiny-mla-moe",
                              "traffic": "tiny_chat", "chips": 1, "why": "test"})
    line = run.execute(spec, "serve.tiny.mla", 2**33 + 41, 2.0, False,
                       devices=jax.devices()[:1], peaks={}, log=lambda *a: None)
    assert line["correct"] is True and line["attempted"] == 24 and line["failed"] == 0
    assert {"attn/w_q", "attn/w_dkv", "attn/w_uk", "attn/w_uv", "attn/w_o", "moe/router",
            "moe/w_gate", "moe/w_up", "moe/w_down", "moe/shared/w_gate", "moe/shared/w_up",
            "moe/shared/w_down", "mlp/w_gate"} <= drawn
    calls = [json.loads(x) for x in stub.with_suffix(".calls").read_text().splitlines()]
    assert calls[0] == {"call": "init", "model": "tiny-mla-moe", "precision": "reference"}
    assert calls[1:] == [{"call": "forward", "rows": 1}] * mix["check_requests"]


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "serve.phi4.chat",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=run.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and "{" not in p.stdout


def test_every_per_layer_metric_has_a_reader():
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    for m in spec["per_layer"]:
        assert callable(run.metric_reader(m["name"]))
    for c in spec["workloads"]:
        assert (run.BENCH / "traffic" / f"{c['traffic']}.json").exists()
        assert (run.BENCH / "limits" / f"{c['name']}.json").exists()


def test_stacked_weights_equal_single_layers():
    key = jax_key(2**35 + 3)
    shapes = {"layers": {"attn": {"w_q": jax.ShapeDtypeStruct((3, 8, 2, 4), jnp.float32)},
                         "ln1": {"scale": jax.ShapeDtypeStruct((3, 8), jnp.float32)}},
              "embedding": {"embed": jax.ShapeDtypeStruct((10, 8), jnp.bfloat16)}}
    tree = jax.jit(lambda k: weights.program_params(k, shapes, {"layers": [4, 5, 6]},
                                                    jnp.float32))(key)
    for j, layer in enumerate([4, 5, 6]):
        one = jax.jit(lambda k, i: weights.leaf(k, "attn/w_q", i, (8, 2, 4), jnp.float32))(
            key, jnp.int32(layer))
        np.testing.assert_array_equal(tree["layers"]["attn"]["w_q"][j], one)
    w = np.asarray(tree["layers"]["attn"]["w_q"])
    assert abs(w.std() - 8 ** -0.5) < 0.05 and np.abs(w).max() <= 8 ** -0.5 * 3 ** 0.5
    assert np.all(np.asarray(tree["layers"]["ln1"]["scale"]) == 1.0)
