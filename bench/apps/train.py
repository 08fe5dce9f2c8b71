"""Training: the jitted ``make_train_step`` under FSDP, fed through the store.

The state (float32 weights drawn from the seed, AdamW moments) is built by
one jitted program straight into ``ShardingRules``' FSDP sharding over every
chip of the cell; the step is compiled once for the cell's batch.  Batches of
Zipf tokens drawn from the seed go through ``ProxyPrefetcher`` and a store,
as ``repro.launch.train`` feeds them; the loop repeats that driver's step
loop without its logging syncs and without checkpoints.

Set-up drives the compiled step through its first three steps on the same
state, feed and call the window uses; the reference follows those three.

Mix keys: ``batch``, ``seq``, ``remat``, ``prefetch``, ``optimizer`` (every
``AdamWConfig`` field, so that the reference reads the same values),
``trace_last_s``.
"""

from __future__ import annotations

import gc
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from bench import generate as gen
from bench import weights
from bench.apps.common import (Env, Outcome, TraceSlice, group_layers, model_config, peak_bytes,
                               reference)

SPANS = ("next_batch", "step")
FIRST_STEPS = 3


def norms(tree, layers: dict[str, list[int]]):
    """Per-layer norms of each leaf (stacked groups: one per layer)."""

    def one(path, x):
        x = x.astype(jnp.float32)
        top = str(getattr(path[0], "key", path[0]))
        axes = tuple(range(1, x.ndim)) if top in layers else None
        return jnp.sqrt(jnp.sum(x * x, axis=axes))

    return jax.tree_util.tree_map_with_path(one, tree)


def build(env: Env):
    from repro.distributed.sharding import ShardingRules
    from repro.launch.mesh import make_data_mesh
    from repro.models import transformer as tx
    from repro.train.optimizer import AdamWConfig, init_opt_state
    from repro.train.train_step import init_train_state, make_train_step

    mix = env.mix
    cfg = model_config(env.model).replace(remat=mix["remat"])
    mesh = make_data_mesh(env.devices)
    rules = ShardingRules(mesh)
    ctx = tx.RunCtx(mesh=mesh, dp_axes=rules.dp_axes, ep_axis="model")
    opt_cfg = AdamWConfig(**mix["optimizer"])
    shapes = jax.eval_shape(lambda k: init_train_state(cfg, k), jax.random.PRNGKey(0))
    state_sh = rules.state_shardings(shapes)
    layers = group_layers(cfg)

    def train_weights(key):
        return weights.program_params(key, shapes["params"], layers, cfg.param_dtype)

    def train_state(key):
        params = train_weights(key)
        return {"params": params, "opt": init_opt_state(params)}

    key = gen.jax_key(env.seed)
    state = jax.jit(train_state, out_shardings=state_sh)(key)
    step_inner = make_train_step(cfg, opt_cfg, ctx)

    def train_step(state, batch):
        return step_inner(state, batch)

    batch_sh = {"tokens": rules.batch_spec(2)}
    step = jax.jit(train_step, in_shardings=(state_sh, batch_sh),
                   out_shardings=(state_sh, None), donate_argnums=(0,)).lower(
        state, {"tokens": jax.ShapeDtypeStruct((mix["batch"], mix["seq"]), np.int32)}
    ).compile()

    @jax.jit
    def change_norms(params, key):
        return norms(jax.tree.map(jnp.subtract, params, train_weights(key)), layers)

    @jax.jit
    def first_grad_norms(m):          # m after one step = (1 - b1) * clipped gradient
        return norms(jax.tree.map(lambda x: x / (1 - opt_cfg.b1), m), layers)

    return state, step, change_norms, first_grad_norms, layers


def per_layer(tree, layers: dict[str, list[int]]) -> dict[str, float]:
    """Norm tree of the program's layout -> ``{"<layer>:<name>"|"<name>": float}``."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        x = np.asarray(x)
        if keys[0] in layers:
            for j, i in enumerate(layers[keys[0]]):
                out[f"{i}:{'/'.join(keys[1:])}"] = float(x[j])
        else:
            out["/".join(keys)] = float(x)
    return out


def run(env: Env) -> Outcome:
    from repro.api import ConnectorSpec, StoreConfig
    from repro.core.store import unregister_store
    from repro.train.data import ProxyPrefetcher

    mix = env.mix
    B, S, V = mix["batch"], mix["seq"], env.model["vocab_size"]
    state, step, change_norms, first_grad_norms, layers = build(env)
    key = gen.jax_key(env.seed)

    def make_batch(i):
        return {"tokens": gen.zipf_tokens(env.seed, i, B, S, V)}

    store_name = f"bench-{env.cell}"
    store = StoreConfig(store_name, ConnectorSpec("memory", segment=store_name)).build(
        register=True)
    fed: list[int] = []                    # crc32 of every batch that reached the step
    losses, steps = [], 0
    tslice = TraceSlice(env, mix["trace_last_s"])
    try:
        with ProxyPrefetcher(store, make_batch, depth=mix["prefetch"]) as pf:

            def one_step(state):
                with TraceAnnotation("next_batch"):
                    tokens = np.asarray(next(pf)["tokens"])
                    fed.append(zlib.crc32(tokens.tobytes()))
                with TraceAnnotation("step"):
                    return step(state, {"tokens": tokens})

            # the first steps: the same state, feed and call as the window's
            state, metrics = one_step(state)
            losses.append(metrics["loss"])
            grad1 = per_layer(first_grad_norms(state["opt"]["m"]), layers)
            for _ in range(FIRST_STEPS - 1):
                state, metrics = one_step(state)
                losses.append(metrics["loss"])
            change3 = per_layer(change_norms(state["params"], key), layers)
            losses = [float(x) for x in losses]
            t0 = time.perf_counter()
            setup_s = t0 - env.t_process
            while time.perf_counter() < t0 + env.seconds:
                tslice.poll(time.perf_counter() - t0)
                state, metrics = one_step(state)
                steps += 1
            jax.block_until_ready(state)
            elapsed = time.perf_counter() - t0
            tslice.finish()
            last_loss = float(metrics["loss"])
    finally:
        store.close()
        unregister_store(store_name)
    peaks = peak_bytes(env.devices)
    reduction = tslice.reduce(SPANS, SPANS)
    del state, metrics, step, change_norms, first_grad_norms
    gc.collect()

    wrong_batches = sum(c != zlib.crc32(make_batch(i)["tokens"].tobytes())
                        for i, c in enumerate(fed))
    readings = reference_readings(env, losses, grad1, change3)
    checks = {"wrong_batches": {"value": wrong_batches, "limit": 0},
              "finite_loss": {"value": 0 if np.isfinite(last_loss) else 1, "limit": 0}}
    for k, v in readings.items():
        checks[k] = {"value": v, "limit": env.limits[k]}
    counters = {"window_steps": steps, "losses": losses, "last_loss": last_loss,
                "tokens_per_step": B * S}
    return Outcome(
        attempted=steps, failed=0,
        e2e={"setup_s": setup_s, "train_tok_s": steps * B * S / elapsed},
        checks=checks, peak_bytes=peaks, counters=counters, reduction=reduction,
        kept={"losses": losses, "grad1": grad1, "change3": change3})


def reference_readings(env: Env, losses, grad1, change3) -> dict[str, float]:
    """The numbers compared with the plain float32 reference's first steps."""
    return compare(_reference(env), losses, grad1, change3)


def _reference(env: Env, precision: str = "reference", rows: int | None = None) -> dict:
    mix = env.mix
    batches = [gen.zipf_tokens(env.seed, i, mix["batch"], mix["seq"],
                               env.model["vocab_size"])[:rows] for i in range(FIRST_STEPS)]
    return reference(env).first_steps(env.model, gen.jax_key(env.seed), batches,
                                      mix["optimizer"], env.devices, precision)


def control_reading(env: Env, outcome: Outcome) -> dict[str, dict[str, float]]:
    """Readings that set the limits' upper ends, each compared with the
    reference as a run's numbers are: the control (the reference computed in
    int8) in the program's place, and two faults planted in the reference:
    half of the batch left out (the mean over the rest), and each chip's
    rows alone (the exchange between chips left out)."""
    ref = _reference(env)
    B, n = env.mix["batch"], len(env.devices)
    out = {}
    for name, kw in (("control", {"precision": "control"}), ("half_batch", {"rows": B // 2}),
                     ("one_chip_rows", {"rows": B // n})):
        r = _reference(env, **kw)
        out[name] = compare(ref, r["losses"], r["grad1"], r["change3"])
    return out


def compare(ref: dict, losses, grad1, change3) -> dict[str, float]:
    """Loss: largest relative gap over the first steps.  Gradient and change:
    the worst leaf's gap between the two norms, over the reference's norm of
    that leaf or the median leaf's, whichever is larger; leaves whose
    reference gradient is under a thousandth of the median leaf's (moved by
    round-off alone) are left out."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses, ref["losses"]))
    g_med = float(np.median(list(ref["grad1"].values())))
    c_med = float(np.median(list(ref["change3"].values())))
    live = [k for k, v in ref["grad1"].items() if v >= 1e-3 * g_med]
    grad_gap = max(abs(grad1[k] - ref["grad1"][k]) / max(ref["grad1"][k], g_med) for k in live)
    change_gap = max(abs(change3[k] - ref["change3"][k]) / max(ref["change3"][k], c_med)
                     for k in live)
    return {"loss_gap": loss_gap, "grad_norm_gap": grad_gap, "change_norm_gap": change_gap}
