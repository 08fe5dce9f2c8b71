"""Readings from which a cell's correctness limits are set.

    python bench/control.py --workload <cell> --seconds <s> --seeds <a>-<b> --control <k>

In one process: for each seed, one run of the cell (a short window at the
cell's own load) and the numbers it compares; for the first ``k`` seeds also
the lower-precision control's reading of the same requests.  The program's
largest reading over the seeds is the limit's lower end, the control's
smallest its upper end.  Prints one JSON line per seed.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import ROOT, chips, find_cell, load_json  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True, help="first-last, inclusive")
    ap.add_argument("--control", type=int, default=3, help="seeds that also run the control")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.apps.common import Env
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    spec = load_json(ROOT / "BENCHMARK.json")
    cell, config = find_cell(spec, args.workload)
    devices, _ = chips(cell["chips"])
    conf = load_json(ROOT / config["file"])
    mix = load_json(ROOT / "bench" / "traffic" / f"{cell['traffic']}.json")
    limits = load_json(ROOT / "bench" / "limits" / f"{args.workload}.json")
    app = importlib.import_module(f"bench.apps.{mix['app']}")
    first, last = (int(x) for x in args.seeds.split("-"))
    for i, seed in enumerate(range(first, last + 1)):
        env = Env(cell=args.workload, model=conf["model"], reference=str(ROOT / conf["reference"]),
                  mix=mix, limits=limits, seed=seed,
                  seconds=args.seconds, trace=False, devices=devices,
                  t_process=time.perf_counter(), out_dir=str(ROOT / "bench" / "out"))
        res = app.run(env)
        row = {"seed": seed, "correct": res.correct, "attempted": res.attempted,
               "failed": res.failed, "program": {k: c["value"] for k, c in res.checks.items()}}
        if i < args.control:
            row.update(app.control_reading(env, res))
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
