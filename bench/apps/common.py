"""What every app shares: the run's inputs, the model configuration as the
program takes it, the configuration's reference, and the profiler slice."""

from __future__ import annotations

import dataclasses
import importlib.util
import os
import shutil
import time
import typing
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


@dataclass
class Env:
    """One run: the cell and its data files, as ``run.py`` found them."""

    cell: str
    model: dict[str, Any]          # the configuration file's ``model`` section
    reference: str                 # path of the configuration's plain reference
    mix: dict[str, Any]            # the traffic file
    limits: dict[str, float]       # the cell's correctness limits
    seed: int
    seconds: float
    trace: bool
    devices: list
    t_process: float               # perf_counter at process start
    out_dir: str                   # scratch space inside the checkout
    log: Any = print


@dataclass
class Outcome:
    """What an app hands back to ``run.py``."""

    attempted: int
    failed: int
    e2e: dict[str, float]                      # end-to-end metrics by name
    checks: dict[str, dict[str, float]]        # name -> {"value", "limit"}
    peak_bytes: list[int]                      # per device, read after the window
    counters: dict[str, Any] = field(default_factory=dict)
    reduction: Any = None                      # tracefile.Reduction of a traced run
    kept: dict[str, Any] = field(default_factory=dict)  # what the control re-reads

    @property
    def correct(self) -> bool:
        return all(c["value"] <= c["limit"] for c in self.checks.values())


def model_config(m: dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration's ``model`` section:
    dtype names become dtypes, every section whose field is a dataclass
    (``ssm``, ``moe``, ``mla``, ...) becomes that dataclass, lists tuples."""
    from repro.models.common import ModelConfig

    hints = typing.get_type_hints(ModelConfig)
    kw = {}
    for k, v in m.items():
        if k in ("param_dtype", "compute_dtype"):
            v = DTYPES[v]
        elif isinstance(v, dict):
            v = next(t for t in typing.get_args(hints[k]) or (hints[k],)
                     if dataclasses.is_dataclass(t))(**v)
        elif isinstance(v, list):
            v = tuple(v)
        kw[k] = v
    return ModelConfig(**kw)


def load_module(path: str | Path, name: str):
    """The Python module in file ``path``, loaded under ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(env: Env):
    """The configuration's reference module (``bench/reference/__init__.py``
    states what it exposes)."""
    return load_module(env.reference, f"bench_reference_{Path(env.reference).stem}")


def group_layers(cfg) -> dict[str, list[int]]:
    """Global layer indices of each of the program's stacked layer groups."""
    from repro.models import transformer as tx

    out, i = {}, 0
    for g in tx.layer_groups(cfg):
        out[g.name] = list(range(i, i + g.count))
        i += g.count
    return out


def peak_bytes(devices) -> list[int]:
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices]


class TraceSlice:
    """Profiler on for the last ``last_s`` seconds of the window, with a host
    span ``trace_window`` that brackets the slice on the trace's own clock.
    The slice ends with the window: stopping the profiler blocks its caller
    for tens of seconds, which must not delay any request due in the window."""

    def __init__(self, env: Env, last_s: float):
        self.dir = os.path.join(env.out_dir, "trace")
        self.start, self.stop = max(0.0, env.seconds - last_s), env.seconds
        self.enabled = env.trace
        self.log = env.log
        self.state = "before"
        self._span = None

    def poll(self, elapsed: float) -> None:
        if not self.enabled:
            return
        if self.state == "before" and elapsed >= self.start:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation("trace_window")
            self._span.__enter__()
            self.state = "on"
        elif self.state == "on" and elapsed >= self.stop:
            self.finish()

    def finish(self) -> None:
        if self.state == "on":
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = "done"

    def reduce(self, span_names, precedence):
        from bench import tracefile

        if self.state != "done":
            return None
        t = time.perf_counter()
        events = tracefile.load(self.dir, list(span_names) + ["trace_window"])
        shutil.rmtree(self.dir, ignore_errors=True)      # traces are large
        w = [e for e in events if e[2] == "trace_window"]
        if not w:
            return None
        window = (w[0][3], w[0][3] + w[0][4])
        red = tracefile.reduce(events, window, precedence)
        self.log(f"trace: {len(events)} events read and reduced in "
                 f"{time.perf_counter() - t:.1f} s")
        return red
