"""Run one cell of the benchmark and print its result as one JSON line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell names its
configuration (``configs[].file``, whose ``model`` section the program runs
and whose ``reference`` key is the path of its plain reference) and its
traffic mix (``bench/traffic/<traffic>.json``, whose ``app`` key names the
app module ``bench/apps/<app>.py``); its correctness limits are
``bench/limits/<cell>.json``; each per-layer metric is read by
``bench/metrics/<metric>.py``.  A run needs the chips its cell asks for:
with none it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


class NoChip(RuntimeError):
    """No accelerator, too few chips, or a chip with no peaks on record."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(spec: dict, name: str) -> tuple[dict, dict]:
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    return cell, config


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise NoChip(f"no peaks on record for device kind {kind!r}")
    return table[kind]


def chips(n: int) -> tuple[list, dict]:
    """The first ``n`` accelerator chips and their peaks, or ``NoChip``."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"first device is {devices[0].platform!r}, not a TPU")
    if len(devices) < n:
        raise NoChip(f"cell needs {n} chips, found {len(devices)}")
    return devices[:n], peaks_for(devices[0].device_kind)


def metric_reader(name: str):
    from bench.apps.common import load_module

    return load_module(BENCH / "metrics" / f"{name}.py", f"bench_metric_{name}").read


def cell_metrics(spec: dict, cell: str) -> tuple[list[dict], list[dict]]:
    """The end-to-end and per-layer metrics that ``cell`` reports."""
    e2e = [m for m in spec["end_to_end"] if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (cell in m["workloads"] if "workloads" in m else m["moves"] in names)]
    return e2e, layer


def execute(spec: dict, cell_name: str, seed: int, seconds: float, trace: bool, *,
            devices=None, peaks=None, model=None, mix=None, limits=None,
            t_process: float = T_PROCESS, log=print) -> dict:
    """One run of ``cell_name``; returns the result line as a dict.

    ``devices``/``peaks`` default to the cell's chips; tests pass CPU devices
    and small ``model``/``mix`` dicts in place of the cell's files.
    """
    from bench.apps.common import Env

    cell, config = find_cell(spec, cell_name)
    if devices is None:
        devices, peaks = chips(cell["chips"])
    conf = load_json(ROOT / config["file"])
    model = model or conf["model"]
    mix = mix or load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    limits = limits or load_json(BENCH / "limits" / f"{cell_name}.json")
    out_dir = BENCH / "out" / cell_name / str(os.getpid())
    out_dir.mkdir(parents=True, exist_ok=True)
    app = importlib.import_module(f"bench.apps.{mix['app']}")
    env = Env(cell=cell_name, model=model, reference=str(ROOT / conf["reference"]),
              mix=mix, limits=limits, seed=seed,
              seconds=seconds, trace=trace, devices=devices, t_process=t_process,
              out_dir=str(out_dir), log=log)
    res = app.run(env)
    e2e, layer = cell_metrics(spec, cell_name)
    line: dict = {"correct": res.correct, "attempted": res.attempted, "failed": res.failed}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind, "count": len(devices),
              "memory_peak_bytes": max(res.peak_bytes)}
    if not trace:
        line["metrics"] = {m["name"]: {"value": res.e2e[m["name"]], "unit": m["unit"]}
                           for m in e2e}
    else:
        red = res.reduction
        ctx = SimpleNamespace(reduction=red, counters=res.counters, model=model, mix=mix,
                              peaks=peaks, chips=len(devices))
        metrics = {}
        for m in layer:
            value = metric_reader(m["name"])(ctx) if red is not None else None
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        line["metrics"] = metrics
        if red is not None:
            device["busy_s"] = sum(red.busy_s) / len(red.busy_s)
            device["window_s"] = red.window_s
            line["breakdown"] = {"device_ops": red.top_ops, "idle_gaps": red.idle_by_span}
    line["device"] = device
    line["checks"] = res.checks
    log(f"peak_bytes_in_use per device: {res.peak_bytes}")
    log(f"run took {time.perf_counter() - t_process:.1f} s from process start")
    log(f"counters: {json.dumps(res.counters, default=str)}")
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    spec = load_json(ROOT / "BENCHMARK.json")
    try:
        line = execute(spec, args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as exc:
        print(f"no chip: {exc}", file=sys.stderr)
        return 3
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(line))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
