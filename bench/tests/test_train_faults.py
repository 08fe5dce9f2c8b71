"""The training cell's run at a size the CPU holds, on four virtual devices:
a sound run is correct, and each fault planted in the timed path makes it
not correct."""

import os
import subprocess
import sys

import pytest

from bench import run

SCRIPT = r'''
import json, sys, time
import jax, numpy as np
from bench import run
from bench.tests.helpers import train_spec, TINY_HYBRID, PEAKS, train_mix
fault = sys.argv[1]
if fault == "unchanged":
    import repro.train.train_step as ts
    make = ts.make_train_step
    def frozen(*a, **kw):
        step = make(*a, **kw)
        return lambda state, batch: (state, step(state, batch)[1])
    ts.make_train_step = frozen
elif fault in ("half_batch", "no_exchange"):
    import repro.train.train_step as ts
    loss = ts._loss
    keep = 2 if fault == "half_batch" else 1      # of 4 rows: half, or one chip's
    def part(cfg, params, batch, ctx):
        return loss(cfg, params, {"tokens": batch["tokens"][:keep]}, ctx)
    ts._loss = part
elif fault == "altered_token":
    import repro.train.data as data
    produce = data.ProxyPrefetcher._produce
    make0 = None
    def altered(self):
        make = self.make_batch
        def mk(i):
            b = make(i)
            b["tokens"][0, 5] = (b["tokens"][0, 5] + 1) % 256
            return b
        self.make_batch = mk
        produce(self)
    data.ProxyPrefetcher._produce = altered
line = run.execute(train_spec(), "train.hymba.fsdp4", 7, 2.0, False, devices=jax.devices()[:4],
                   peaks=PEAKS, model=TINY_HYBRID, mix=train_mix(),
                   limits={"loss_gap": 1e-3, "grad_norm_gap": 1e-3, "change_norm_gap": 1e-3},
                   t_process=time.perf_counter(), log=lambda *a: None)
print(json.dumps(line))
'''


def _run(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=f"{run.ROOT / 'src'}:{run.ROOT}")
    p = subprocess.run([sys.executable, "-c", SCRIPT, fault], cwd=run.ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    import json
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_training_run_is_correct():
    line = _run("none")
    assert line["correct"], line["checks"]
    assert line["device"]["count"] == 4 and line["metrics"]["train_tok_s"]["value"] > 0


@pytest.mark.parametrize("fault,check", [
    ("unchanged", "change_norm_gap"),
    ("half_batch", "loss_gap"),
    ("no_exchange", "loss_gap"),
    ("altered_token", "wrong_batches"),
])
def test_fault_is_caught(fault, check):
    line = _run(fault)
    assert not line["correct"]
    c = line["checks"][check]
    assert c["value"] > c["limit"], line["checks"]
