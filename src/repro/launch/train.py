"""Production training driver.

Composes every layer of the framework: mesh + sharding rules, jitted train
step with explicit in/out shardings, the proxy-fed data pipeline, async
proxy-backed checkpointing with restart, and failure-tolerant stepping.

By default the mesh is ``(data=n, model=1)`` over every device of the
process, with the state FSDP-sharded over ``data``; on the CPU the same code
runs on one device -- the examples wrap exactly this entry point.

    PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m \
        --steps 200 --batch 8 --seq 256 --smoke
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path
from typing import Any

import jax
import numpy as np

from repro.api import ConnectorSpec, StoreConfig
from repro.configs import get_config, get_smoke_config
from repro.distributed.sharding import ShardingRules
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import make_data_mesh, make_production_mesh
from repro.models.common import ModelConfig
from repro.models import transformer as tx
from repro.train.checkpoint import CheckpointManager
from repro.train.data import ProxyPrefetcher, synthetic_batch
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import init_train_state, make_train_step


def build_mesh(args) -> jax.sharding.Mesh:
    if args.production:
        return make_production_mesh(multi_pod=args.multi_pod)
    return make_data_mesh()


def train(
    args,
    *,
    cfg: ModelConfig | None = None,
    mesh: jax.sharding.Mesh | None = None,
) -> dict[str, Any]:
    """Run ``args.steps`` steps; ``cfg`` and ``mesh`` default to what
    ``args`` names (an in-process caller passes them to run a cut config or
    the same run on a chosen set of devices)."""
    setup_compile_cache()
    if cfg is None:
        cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    if args.num_microbatches:
        cfg = cfg.replace(num_microbatches=args.num_microbatches)
    if args.remat:
        cfg = cfg.replace(remat=args.remat)

    mesh = mesh if mesh is not None else build_mesh(args)
    rules = ShardingRules(mesh, fsdp_pod=args.fsdp_pod)
    ctx = tx.RunCtx(mesh=mesh, dp_axes=rules.dp_axes, ep_axis="model")

    # -- store / checkpoint / data (the paper's layer) ------------------------
    run_dir = Path(args.run_dir)
    run_dir.mkdir(parents=True, exist_ok=True)
    if args.connector == "sharded":
        spec = ConnectorSpec("sharded", store_dir=str(run_dir / "objects"),
                             num_shards=8)
    else:
        spec = ConnectorSpec("memory", segment=f"train-{args.arch}")
    store = StoreConfig(f"train-{args.arch}", spec).build(register=True)
    ckpt = CheckpointManager(store, str(run_dir / "ckpt_index.json"),
                             keep=args.keep_checkpoints)

    # -- state: fresh or restored (crash/preemption restart) -------------------
    # Warm up over a tenth of the run at most: a warmup longer than the run
    # never reaches the learning rate asked for.
    opt_cfg = AdamWConfig(
        lr=args.lr, total_steps=args.steps,
        warmup_steps=min(AdamWConfig.warmup_steps, args.steps // 10),
    )
    key = jax.random.PRNGKey(args.seed)

    def init(k):
        return init_train_state(cfg, k)

    state_sh = rules.state_shardings(jax.eval_shape(init, key))
    restored = None if args.fresh else ckpt.restore()
    if restored is not None:
        start_step, host_state = restored
        state = jax.device_put(host_state, state_sh)
        print(f"[restore] resumed from step {start_step}", flush=True)
    else:
        start_step = 0
        # Built by one program straight into its sharding: no device ever
        # holds more than its shard of the state.
        state = jax.jit(init, out_shardings=state_sh)(key)

    batch_sh = {"tokens": rules.batch_spec(2)}
    t0 = time.perf_counter()
    step_fn = jax.jit(
        make_train_step(cfg, opt_cfg, ctx),
        in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, None),
        donate_argnums=(0,),
    ).lower(
        state, {"tokens": jax.ShapeDtypeStruct((args.batch, args.seq), np.int32)}
    ).compile()
    compile_s = time.perf_counter() - t0

    def make_batch(i):
        return synthetic_batch(
            np.random.default_rng(args.seed * 100_003 + i),
            args.batch, args.seq, cfg.vocab_size,
        )

    metrics_log: list[dict] = []
    t_start = time.perf_counter()
    with ProxyPrefetcher(store, make_batch, depth=args.prefetch) as pf:
        for step, proxy in zip(range(start_step, args.steps), pf):
            batch = {"tokens": np.asarray(proxy["tokens"])}
            state, metrics = step_fn(state, batch)
            if step % args.log_every == 0 or step == args.steps - 1:
                loss = float(metrics["loss"])
                dt = time.perf_counter() - t_start
                tok_s = (step - start_step + 1) * args.batch * args.seq / dt
                print(
                    f"[step {step:5d}] loss={loss:.4f} "
                    f"tokens/s={tok_s:,.0f}", flush=True,
                )
                metrics_log.append(
                    {"step": step, "loss": loss, "tokens_per_s": tok_s}
                )
            if args.ckpt_every and step and step % args.ckpt_every == 0:
                ckpt.save(step, state)  # async, off the step path
    if args.ckpt_every:
        ckpt.save(args.steps, state, blocking=True)

    (run_dir / "metrics.json").write_text(json.dumps(metrics_log, indent=1))
    return {"final": metrics_log[-1] if metrics_log else None,
            "log": metrics_log, "compile_s": compile_s, "state": state,
            "checkpoints": ckpt}


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-130m")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--production", action="store_true",
                    help="use the 16x16 v5e-256 pod mesh (256 devices; "
                         "512 with --multi-pod)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--fsdp-pod", action="store_true")
    ap.add_argument("--num-microbatches", type=int, default=0)
    ap.add_argument("--remat", default="")
    ap.add_argument("--connector", choices=["memory", "sharded"],
                    default="sharded")
    ap.add_argument("--run-dir", default="artifacts/train_run")
    ap.add_argument("--prefetch", type=int, default=2)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-every", type=int, default=50,
                    help="async checkpoint every N steps plus a blocking one "
                         "at the end; 0 saves none")
    ap.add_argument("--keep-checkpoints", type=int, default=3)
    ap.add_argument("--fresh", action="store_true", help="ignore checkpoints")
    return ap.parse_args(argv)


if __name__ == "__main__":
    train(parse_args())
