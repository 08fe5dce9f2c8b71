"""Serving: an open loop of requests through ``Session.serve``.

The client sends each prompt on the ``requests`` topic at its scheduled time
and reads the reply off the ``responses`` topic; ``ModelServer`` batches the
requests and calls ``generate``, which runs the jitted ``tx.prefill`` and
``tx.decode_step``.  ``generate`` repeats the batch closure of
``repro.launch.serve`` (pad to the compiled batch, a fresh cache per batch,
greedy tokens one host dispatch at a time), without that driver's timing
syncs: the latency the benchmark reports is the client's.

Mix keys: ``prompt_len``, ``new_tokens``, ``rate_per_s``, ``max_batch_size``,
``max_wait_ms``, ``queue_depth``, ``check_requests``, ``trace_last_s``.
"""

from __future__ import annotations

import gc
import math
import threading
import time
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from bench import generate as gen
from bench import weights
from bench.apps.common import (DTYPES, Env, Outcome, TraceSlice, group_layers, model_config,
                               peak_bytes, reference)
from bench.measure import percentile

# Host spans, in the order an idle gap on the device is given to them.
SPANS = ("decode_token", "prefill", "batch", "send", "wait_request")
GRACE_S = 60.0   # how long past the window's close an answer may still come


def next_token(logits: jax.Array) -> jax.Array:
    """Greedy choice, as ``repro.launch.serve`` makes it."""
    return jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)


def build(env: Env):
    """Weights from the seed and the compiled prefill and decode programs."""
    from repro.distributed.sharding import ShardingRules
    from repro.launch.mesh import make_data_mesh
    from repro.models import transformer as tx

    mix = env.mix
    B, PL, G = mix["max_batch_size"], mix["prompt_len"], mix["new_tokens"]
    cfg = model_config(env.model)
    mesh = make_data_mesh(env.devices)
    rules = ShardingRules(mesh, fsdp_params=False)          # the serving layout
    ctx = tx.RunCtx(mesh=mesh, dp_axes=rules.dp_axes, ep_axis="model", decode=True)
    shapes = jax.eval_shape(lambda k: tx.init_params(cfg, k), jax.random.PRNGKey(0))
    layers = group_layers(cfg)

    def serve_weights(key):
        return weights.program_params(key, shapes, layers, cfg.param_dtype)

    params = jax.jit(serve_weights, out_shardings=rules.state_shardings(shapes))(
        gen.jax_key(env.seed))

    def serve_prefill(p, t, c):
        return tx.prefill(cfg, p, t, c, ctx)

    def serve_decode(p, c, t, pos):
        return tx.decode_step(cfg, p, c, t, pos, ctx)

    toks0 = jnp.zeros((B, PL), jnp.int32)
    cache0 = tx.init_cache(cfg, B, PL + G + 1)
    prefill_jit = jax.jit(serve_prefill)
    prefill = prefill_jit.lower(params, toks0, cache0).compile()
    _, cache_shape = jax.eval_shape(prefill_jit, params, toks0, cache0)
    decode = jax.jit(serve_decode).lower(
        params, cache_shape, jnp.zeros((B, 1), jnp.int32), jnp.zeros((B, 1), jnp.int32)
    ).compile()
    del toks0, cache0

    def generate(prompts: list) -> list:
        with TraceAnnotation("batch"):
            k = len(prompts)
            toks = np.stack([np.asarray(p, np.int32) for p in prompts])
            if k < B:
                toks = np.concatenate([toks, np.zeros((B - k, PL), np.int32)])
            cache = tx.init_cache(cfg, B, PL + G + 1)
            with TraceAnnotation("prefill"):
                logits, cache = prefill(params, jnp.asarray(toks), cache)
                tok = next_token(logits)
            out = [tok]
            for i in range(G - 1):
                with TraceAnnotation("decode_token"):
                    pos = jnp.full((B, 1), PL + i, jnp.int32)
                    logits, cache = decode(params, cache, tok, pos)
                    tok = next_token(logits)
                out.append(tok)
            full = np.asarray(jnp.concatenate(out, axis=1))
            return [full[i] for i in range(k)]

    return generate, params


def run(env: Env) -> Outcome:
    from repro.api import ClusterSpec, ServeSpec, Session

    mix = env.mix
    B, PL, G = mix["max_batch_size"], mix["prompt_len"], mix["new_tokens"]
    vocab = env.model["vocab_size"]
    generate, params = build(env)
    sched = gen.open_loop_schedule(env.seed, mix["rate_per_s"], env.seconds)
    n = len(sched)
    prompts = gen.prompts(env.seed, n, PL, vocab)
    # Every program and every eager op of the batch runs once before the
    # window, outside the server, so that its stats hold only the window.
    generate([prompts[0]] * B)

    spec = ClusterSpec(n_workers=1, serve=ServeSpec(
        B, max_wait_ms=mix["max_wait_ms"], queue_depth=mix["queue_depth"]))
    replies: dict[str, list] = defaultdict(list)
    key_of: dict[str, int] = {}
    late = np.zeros(n)
    tslice = TraceSlice(env, mix["trace_last_s"])
    with Session(cluster=spec, name=f"bench-{env.cell}") as session:
        server = session.serve(generate)
        server.attach(session.stream_consumer("requests"),
                      session.stream_producer("responses"))
        requests = session.stream_producer("requests")
        responses = session.stream_consumer("responses")

        def receive():
            for item in responses:
                replies[item.metadata["key"]].append(
                    (time.perf_counter(), item.metadata.get("status"), item.value))

        receiver = threading.Thread(target=receive, name="bench-receiver", daemon=True)
        receiver.start()
        t0 = time.perf_counter()
        setup_s = t0 - env.t_process
        for i, due in enumerate(sched):
            with TraceAnnotation("wait_request"):
                while True:
                    tslice.poll(time.perf_counter() - t0)
                    left = t0 + due - time.perf_counter()
                    if left <= 0:
                        break
                    time.sleep(min(left, 0.05))
            with TraceAnnotation("send"):
                late[i] = time.perf_counter() - (t0 + due)
                key_of[requests.send(prompts[i])] = i
        while time.perf_counter() < t0 + env.seconds:
            tslice.poll(time.perf_counter() - t0)
            time.sleep(0.01)
        stats = server.stats()       # the batcher's counters over the window
        tslice.finish()
        deadline = t0 + env.seconds + GRACE_S
        while len(replies) < n and time.perf_counter() < deadline:
            time.sleep(0.01)
        requests.close()             # end of stream: the pump flushes, replies close
        receiver.join(timeout=30.0)
    peaks = peak_bytes(env.devices)
    reduction = tslice.reduce(SPANS, SPANS)
    del generate, params
    gc.collect()

    # -- what came back ---------------------------------------------------------
    latency = np.full(n, math.inf)
    served: dict[int, np.ndarray] = {}
    misrouted = errors = 0
    for key, got in list(replies.items()):
        i = key_of.get(key)
        if i is None or len(got) != 1:
            misrouted += 1
            continue
        t, status, value = got[0]
        if status != "ok":
            errors += status == "error"
            continue
        value = np.asarray(value)
        if value.shape != (G,) or value.min() < 0 or value.max() >= vocab:
            misrouted += 1
            continue
        latency[i] = t - (t0 + sched[i])
        served[i] = value
    unanswered = n - sum(1 for k in key_of if k in replies)
    t_check = time.perf_counter()
    gap = mean_logit_gap(env, prompts, served)
    env.log(f"reference check of {env.mix['check_requests']} requests: "
            f"{time.perf_counter() - t_check:.1f} s")
    checks = {
        "unanswered": {"value": unanswered, "limit": 0},
        "misrouted": {"value": misrouted, "limit": 0},
        "errors": {"value": errors, "limit": 0},
        "mean_logit_gap": {"value": gap, "limit": env.limits["mean_logit_gap"]},
    }
    q = max(1, n // 4)
    counters = {**stats, "max_batch_size": B, "late_p95_s": percentile(late, 0.95),
                "late_max_s": float(late.max()), "requests": n,
                # a backlog that grows through the window shows as a rising latency
                "latency_trend_s": percentile(latency[-q:], 0.5) - percentile(latency[:q], 0.5)}
    return Outcome(
        attempted=n, failed=n - len(served),
        e2e={"setup_s": setup_s, "req_p50_s": percentile(latency, 0.5),
             "req_p95_s": percentile(latency, 0.95)},
        checks=checks, peak_bytes=peaks, counters=counters, reduction=reduction,
        kept={"prompts": prompts, "served": served})


def control_reading(env: Env, outcome: Outcome) -> dict[str, dict[str, float]]:
    """The control's reading on the requests ``outcome`` served."""
    gap = mean_logit_gap(env, outcome.kept["prompts"], outcome.kept["served"],
                         precision="control")
    return {"control": {"mean_logit_gap": gap}}


def sample_sequences(env: Env, prompts: np.ndarray, served: dict[int, np.ndarray]):
    """The checked requests (drawn from the seed) as full token rows: the
    prompt, then every served token but the last."""
    idx = sorted(served)
    pick = [idx[j] for j in gen.sample(env.seed, len(idx), env.mix["check_requests"])]
    rows = np.stack([np.concatenate([prompts[i], served[i][:-1]]) for i in pick])
    return rows, np.stack([served[i] for i in pick])


def mean_logit_gap(env: Env, prompts, served, precision: str = "reference") -> float:
    """Mean gap by which a served token's reference logit lies below the
    reference's best at its position, over the sampled requests.  (The widest
    gap is not compared: it does not separate the program from the control,
    PERF.md section 2.)  The reference is the configuration's own.

    With ``precision="control"`` the lower-precision control stands in for the
    program: it reads the same rows and its own first choice is judged.
    """
    if not served:
        return math.inf
    rows, tokens = sample_sequences(env, prompts, served)
    PL = env.mix["prompt_len"]
    read = slice(PL - 1, rows.shape[1])
    key = gen.jax_key(env.seed)
    wdt = DTYPES[env.model["param_dtype"]]           # the weights as served
    make = reference(env).Reference
    ref = make(env.model, weight_dtype=wdt, precision="reference")
    ctl = make(env.model, weight_dtype=wdt, precision="control") if precision == "control" else None
    gaps = []
    for r in range(rows.shape[0]):                 # one request at a time: it fits
        lg = ref.forward(key, rows[r:r + 1], read)[0]
        pick = tokens[r] if ctl is None else jnp.argmax(
            ctl.forward(key, rows[r:r + 1], read)[0], axis=-1)
        chosen = jnp.take_along_axis(lg, jnp.asarray(pick)[:, None], axis=-1)[:, 0]
        gaps.append(np.asarray(lg.max(axis=-1) - chosen))
    return float(np.concatenate(gaps).mean())
