import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
os.environ["JAX_PLATFORMS"] = "cpu"  # 512 fake host devices; never the chip

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: the production
mesh is built from 512 forced CPU devices (the lines above MUST run
before any other import), every cell's step is jitted with explicit in/out
shardings, compiled, and its memory/cost/collective profile is written to
``artifacts/dryrun/<arch>__<shape>__<mesh>.json``.

Usage::

    python -m repro.launch.dryrun --arch granite-20b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all            # every runnable cell, resumable
    python -m repro.launch.dryrun --all --subprocess   # one process per cell
"""

import argparse
import json
import re
import subprocess
import sys
import time
import traceback
from pathlib import Path

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(text: str) -> int:
    """Sum byte sizes of every `dtype[dims]` shape in an HLO result type."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum output-shape bytes of every collective op in (post-SPMD) HLO."""
    out: dict[str, int] = {op: 0 for op in COLLECTIVE_OPS}
    out["count"] = 0
    for line in hlo_text.splitlines():
        stripped = line.strip()
        # e.g.:  %all-reduce.5 = f32[16,128]{1,0} all-reduce(...)
        m = re.match(r"%?[\w.\-]+\s*=\s*(.+?)\s+([\w\-]+)\(", stripped)
        if not m:
            continue
        result_type, opname = m.groups()
        base = opname.rstrip("-start").rstrip("-done")
        for op in COLLECTIVE_OPS:
            if opname == op or opname == op + "-start" or base == op:
                out[op] += _shape_bytes(result_type)
                out["count"] += 1
                break
    return out


def run_cell(arch: str, shape: str, mesh_kind: str, overrides: dict | None = None) -> dict:
    import jax

    from repro.distributed.sharding import ShardingRules
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import cell_skip_reason, make_cell

    skip = cell_skip_reason(arch, shape)
    if skip:
        return {"arch": arch, "shape": shape, "mesh": mesh_kind, "skipped": skip}

    # overrides prefixed "shard:" steer ShardingRules; the rest is ModelConfig
    overrides = dict(overrides or {})
    shard_kw = {
        k.split(":", 1)[1]: v for k, v in overrides.items() if k.startswith("shard:")
    }
    overrides = {k: v for k, v in overrides.items() if not k.startswith("shard:")}

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rules = ShardingRules(mesh, **shard_kw)
    cell = make_cell(arch, shape, rules, overrides)

    t0 = time.monotonic()
    with mesh:
        jitted = jax.jit(
            cell.step_fn,
            in_shardings=cell.in_shardings,
            out_shardings=cell.out_shardings,
            donate_argnums=cell.donate_argnums,
        )
        lowered = jitted.lower(*cell.args)
        t_lower = time.monotonic() - t0
        t0 = time.monotonic()
        compiled = lowered.compile()
        t_compile = time.monotonic() - t0

    mem = compiled.memory_analysis()
    mem_out = {}
    if mem is not None:
        for field in (
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "temp_size_in_bytes",
            "generated_code_size_in_bytes",
            "alias_size_in_bytes",
        ):
            v = getattr(mem, field, None)
            if v is not None:
                mem_out[field] = int(v)

    cost = compiled.cost_analysis() or {}
    cost_out = {
        k: float(v)
        for k, v in cost.items()
        if isinstance(v, (int, float)) and (
            k in ("flops", "transcendentals") or k.startswith("bytes accessed")
        )
    }

    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    # trip-count-corrected structural profile (scan bodies multiplied out)
    from repro.launch.hlo_analysis import analyze

    corrected = analyze(hlo)
    corrected.pop("while_trips", None)

    result = {
        "arch": arch,
        "shape": shape,
        "mesh": mesh_kind,
        "devices": int(mesh.size),
        "mesh_shape": {k: int(v) for k, v in mesh.shape.items()},
        "meta": cell.meta,
        "lower_seconds": round(t_lower, 2),
        "compile_seconds": round(t_compile, 2),
        "memory_analysis": mem_out,
        "cost_analysis": cost_out,
        "collectives": coll,
        "hlo_analysis": corrected,
        "hlo_bytes": len(hlo),
        "overrides": {**overrides, **{f"shard:{k}": v for k, v in shard_kw.items()}},
    }
    return result


def _artifact_path(arch: str, shape: str, mesh_kind: str, tag: str = "") -> Path:
    suffix = f"__{tag}" if tag else ""
    return ARTIFACTS / f"{arch}__{shape}__{mesh_kind}{suffix}.json"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--subprocess", action="store_true",
                    help="run each cell in a fresh interpreter (bounded memory)")
    ap.add_argument("--overrides", type=json.loads, default=None,
                    help='JSON dict of ModelConfig overrides (perf experiments)')
    ap.add_argument("--tag", default="", help="artifact suffix for experiments")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    ARTIFACTS.mkdir(parents=True, exist_ok=True)

    if args.all:
        from repro.configs import list_archs
        from repro.launch.specs import SHAPES

        cells = [
            (a, s, m)
            for a in list_archs()
            for s in SHAPES
            for m in ("single", "multi")
        ]
        failures = 0
        for arch, shape, mesh_kind in cells:
            path = _artifact_path(arch, shape, mesh_kind)
            if path.exists() and not args.force:
                print(f"[skip-cached] {path.name}")
                continue
            if args.subprocess:
                cmd = [
                    sys.executable, "-m", "repro.launch.dryrun",
                    "--arch", arch, "--shape", shape, "--mesh", mesh_kind,
                ]
                if args.force:
                    cmd.append("--force")
                print(f"[cell] {arch} x {shape} x {mesh_kind} ...", flush=True)
                rc = subprocess.call(cmd)
                failures += rc != 0
            else:
                rc = _run_and_write(arch, shape, mesh_kind, None, "")
                failures += rc != 0
        return 1 if failures else 0

    if not args.arch or not args.shape:
        ap.error("--arch and --shape required unless --all")
    return _run_and_write(args.arch, args.shape, args.mesh, args.overrides, args.tag,
                          force=args.force)


def _run_and_write(arch, shape, mesh_kind, overrides, tag, force=False) -> int:
    path = _artifact_path(arch, shape, mesh_kind, tag)
    if path.exists() and not force and not overrides:
        print(f"[skip-cached] {path.name}")
        return 0
    try:
        t0 = time.monotonic()
        result = run_cell(arch, shape, mesh_kind, overrides)
        result["wall_seconds"] = round(time.monotonic() - t0, 2)
        path.write_text(json.dumps(result, indent=1))
        if "skipped" in result:
            print(f"[SKIP] {arch} x {shape} x {mesh_kind}: {result['skipped']}")
        else:
            ca = result["cost_analysis"]
            print(
                f"[OK] {arch} x {shape} x {mesh_kind}: "
                f"flops={ca.get('flops', 0):.3e} "
                f"compile={result['compile_seconds']}s"
            )
        return 0
    except Exception as exc:  # noqa: BLE001 - report and record the failure
        traceback.print_exc()
        path.with_suffix(".error.json").write_text(
            json.dumps({"arch": arch, "shape": shape, "mesh": mesh_kind,
                        "error": f"{type(exc).__name__}: {exc}"})
        )
        return 1


if __name__ == "__main__":
    sys.exit(main())
