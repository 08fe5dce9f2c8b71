"""A run whose timed path is broken underneath comes out not correct, and the
lower-precision control reads far above the program on the same requests."""

import itertools

import jax

from bench import run
from bench.apps import serve
from bench.tests.helpers import TINY, chat_mix, run_serve


def test_sound_run_is_correct():
    line = run_serve(seed=11)
    assert line["correct"] and line["checks"]["mean_logit_gap"]["value"] <= 1e-6


def test_altered_token_is_caught(monkeypatch):
    from repro.models import transformer as tx

    decode = tx.decode_step

    def altered(*a, **kw):              # every decoded token becomes id 7
        logits, cache = decode(*a, **kw)
        return logits.at[..., 7].add(1e4), cache

    monkeypatch.setattr(tx, "decode_step", altered)
    line = run_serve(seed=12)
    assert not line["correct"] and line["checks"]["mean_logit_gap"]["value"] > 1e-4


def test_misrouted_answers_are_caught(monkeypatch):
    from repro.runtime import serving

    init = serving.ModelServer.__init__
    prev = []

    def swapped(self, model_fn, **kw):  # each request gets another prompt's answer
        def fn(prompts):
            others = prompts[1:] + (prev[-1:] or prompts[:1])
            prev[:] = prompts[-1:]
            return model_fn(others)
        init(self, fn, **kw)

    monkeypatch.setattr(serving.ModelServer, "__init__", swapped)
    line = run_serve(seed=13)
    assert not line["correct"] and line["checks"]["mean_logit_gap"]["value"] > 1e-4


def test_dropped_replies_are_caught(monkeypatch):
    from repro.runtime import stream

    send = stream.StreamProducer.send
    n = itertools.count()

    def lossy(self, value, *, metadata=None, **kw):
        if self.topic == "responses" and next(n) % 5 == 0:
            return "dropped"
        return send(self, value, metadata=metadata, **kw)

    monkeypatch.setattr(stream.StreamProducer, "send", lossy)
    line = run_serve(seed=14)
    assert not line["correct"] and line["checks"]["unanswered"]["value"] > 0


SMALL_BF16 = dict(TINY, d_model=256, num_heads=4, num_kv_heads=2, head_dim=64, d_ff=512,
                  num_layers=4, vocab_size=8192, param_dtype="bfloat16",
                  compute_dtype="bfloat16")


def test_control_reads_above_the_program():
    """The control (the reference computed in int8) against the bf16 program
    on the same served requests, at a size the CPU holds: the control's
    smallest mean gap over three seeds is above the program's largest.  The
    chip's readings at the cell's own size are in PERF.md."""
    from bench.apps.common import Env

    mix = chat_mix(check_requests=8, rate_per_s=4.0, new_tokens=32, max_batch_size=8)
    prog, ctl = [], []
    for seed in (21, 22, 23):
        env = Env(cell="serve.phi4.chat", model=SMALL_BF16,
                  reference=str(run.ROOT / "bench" / "reference" / "dense.py"), mix=mix,
                  limits={"mean_logit_gap": 1.0}, seed=seed, seconds=2.0,
                  trace=False, devices=jax.devices()[:1], t_process=0.0, out_dir="",
                  log=lambda *a: None)
        res = serve.run(env)
        prog.append(res.checks["mean_logit_gap"]["value"])
        ctl.append(serve.control_reading(env, res)["control"]["mean_logit_gap"])
    assert min(ctl) > 2 * max(prog), (prog, ctl)
