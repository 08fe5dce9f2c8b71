"""The serve and train entry points, in-process on the CPU, and what
``chip_smoke.py`` relies on: the device check, the kernel mode and the
compile-cache placement."""

from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels import interpret_mode
from repro.launch import serve as serve_mod
from repro.launch import train as train_mod
from repro.launch.compile_cache import CHECKOUT_CACHE_DIR, setup_compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _cache_left_alone(monkeypatch, tmp_path):
    # With the variable set, serve() and train() leave JAX's cache config alone.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))


def test_serve_smoke_answers_every_request():
    args = serve_mod.parse_args([
        "--smoke", "--batch", "2", "--prompt-len", "8", "--gen", "4",
        "--requests", "5",
    ])
    out = serve_mod.serve(args)
    vocab = get_smoke_config(args.arch).vocab_size
    assert out["requests"] == 5 and len(out["outputs"]) == 5
    for toks in out["outputs"].values():
        assert toks.shape == (4,) and 0 <= toks.min() and toks.max() < vocab


def _train_args(run_dir, steps, ckpt_every):
    return train_mod.parse_args([
        "--smoke", "--steps", str(steps), "--batch", "2", "--seq", "32",
        "--ckpt-every", str(ckpt_every), "--log-every", "1",
        "--run-dir", str(run_dir),
    ])


def test_train_smoke_checkpoints_restores_and_resumes(tmp_path):
    out = train_mod.train(_train_args(tmp_path, 4, 2))
    assert [m["step"] for m in out["log"]] == [0, 1, 2, 3]
    assert np.isfinite([m["loss"] for m in out["log"]]).all()
    ckpt = out["checkpoints"]
    assert ckpt.restore(2) is not None  # the async save
    step, restored = ckpt.restore()     # the final blocking save
    assert step == 4
    for got, want in zip(jax.tree.leaves(restored), jax.tree.leaves(out["state"])):
        np.testing.assert_array_equal(got, np.asarray(want))

    # A second run in the same directory resumes from the last checkpoint.
    again = train_mod.train(_train_args(tmp_path, 6, 2))
    assert [m["step"] for m in again["log"]] == [4, 5]


def test_train_ckpt_every_zero_saves_nothing(tmp_path):
    out = train_mod.train(_train_args(tmp_path, 2, 0))
    assert len(out["log"]) == 2 and out["checkpoints"].latest_step() is None


def test_chip_smoke_device_phase_refuses_the_cpu():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    with pytest.raises(RuntimeError, match="no TPU"):
        chip_smoke.phase_device()


def test_compile_cache_follows_the_environment(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert setup_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert setup_compile_cache() == str(CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
        assert CHECKOUT_CACHE_DIR == ROOT / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize(
    "platform,interpret", [("cpu", True), ("tpu", False), ("gpu", None)]
)
def test_kernel_mode_is_chosen_by_platform(monkeypatch, platform, interpret):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    if interpret is None:
        with pytest.raises(RuntimeError, match="gpu"):
            interpret_mode()
    else:
        assert interpret_mode() is interpret
