"""Production serving driver: continuous-batching decode behind the
streaming data plane, with proxy-restored weights.

Composes: lazy checkpoint restore (pytree of proxies -- each host resolves
just-in-time), jitted prefill + decode_step with serving shardings
(``fsdp_params=False``: TP + replication, no per-token weight gathers),
and the runtime's :class:`~repro.runtime.serving.ModelServer`: requests
ride a stream topic (prompt bytes through the cluster store tiers, only
metadata events on the broker), the dynamic batcher groups them up to
``--batch`` within ``--max-wait-ms``, and generated tokens flow back on a
reply topic.  Batching knobs travel declaratively as
``ClusterSpec(serve=ServeSpec(...))``.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
        --batch 4 --prompt-len 16 --gen 32
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import ClusterSpec, ConnectorSpec, ServeSpec, Session, StoreConfig
from repro.configs import get_config, get_smoke_config
from repro.core import is_proxy
from repro.distributed.sharding import ShardingRules
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import make_data_mesh
from repro.models import transformer as tx
from repro.models import whisper as wh
from repro.train.checkpoint import CheckpointManager


def _load_params(args, cfg, rules):
    """Weights from the checkpoint store (lazy proxies) or fresh init, placed
    in the serving layout."""
    init = wh.init_params if cfg.is_encdec else tx.init_params
    key = jax.random.PRNGKey(0)
    shapes = jax.eval_shape(lambda k: init(cfg, k), key)
    shardings = rules.state_shardings(shapes)
    if args.run_dir:
        store = StoreConfig(
            f"train-{args.arch}",
            ConnectorSpec("sharded", store_dir=f"{args.run_dir}/objects",
                          num_shards=8),
        ).build(register=True)
        ckpt = CheckpointManager(store, f"{args.run_dir}/ckpt_index.json")
        restored = ckpt.restore_lazy()
        if restored is None:
            raise SystemExit(f"no checkpoint under {args.run_dir}")
        step, lazy = restored
        params = lazy["params"] if "params" in lazy else lazy
        params = jax.tree.map(
            lambda p, s: np.asarray(p, s.dtype), params, shapes, is_leaf=is_proxy
        )
        print(f"[restore] lazily resolved step-{step} weights by proxy")
        return jax.device_put(params, shardings)
    # One program builds every weight in place on the device: op-by-op init
    # would hold each weight's random-bits temporaries beside the weights.
    return jax.jit(lambda k: init(cfg, k), out_shardings=shardings)(key)


def serve(args) -> dict:
    setup_compile_cache()
    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    # Weights are held in the dtype the model computes in, as served
    # checkpoints are.  From f32 weights the compiler hoists a compute-dtype
    # copy of every weight out of the layer loop: for qwen2.5-3b 11.5 GiB
    # plus 5.75 GiB, more than a v5e chip's 15.75 GiB.
    cfg = cfg.replace(param_dtype=cfg.compute_dtype)
    mesh = make_data_mesh()
    rules = ShardingRules(mesh, fsdp_params=False)  # serving layout
    ctx = tx.RunCtx(mesh=mesh, dp_axes=rules.dp_axes, ep_axis="model",
                    decode=True)
    params = _load_params(args, cfg, rules)

    B, PL, G = args.batch, args.prompt_len, args.gen
    n_req = args.requests or 2 * B

    # Compile both programs before the server starts, so that no request
    # waits on a compilation and the compile time is reported on its own.
    t0 = time.perf_counter()
    toks0 = jnp.zeros((B, PL), jnp.int32)
    cache0 = tx.init_cache(cfg, B, PL + G + 1)
    prefill_jit = jax.jit(lambda p, t, c: tx.prefill(cfg, p, t, c, ctx))
    decode_jit = jax.jit(
        lambda p, c, t, pos: tx.decode_step(cfg, p, c, t, pos, ctx)
    )
    prefill = prefill_jit.lower(params, toks0, cache0).compile()
    _, cache_shape = jax.eval_shape(prefill_jit, params, toks0, cache0)
    decode = decode_jit.lower(
        params, cache_shape, jnp.zeros((B, 1), jnp.int32),
        jnp.zeros((B, 1), jnp.int32),
    ).compile()
    del toks0, cache0
    compile_s = time.perf_counter() - t0
    timings = {"prefill_s": 0.0, "decode_s": 0.0, "decoded": 0}

    def generate(prompts: list) -> list:
        """Batched forward for the server: pad to the fixed serving width
        (one compiled program), prefill once, step the KV cache."""
        k = len(prompts)
        toks = np.stack([np.asarray(p, np.int32) for p in prompts])
        if k < B:
            toks = np.concatenate([toks, np.zeros((B - k, PL), np.int32)])
        cache = tx.init_cache(cfg, B, PL + G + 1)
        t0 = time.perf_counter()
        logits, cache = prefill(params, jnp.asarray(toks), cache)
        jax.block_until_ready(logits)
        timings["prefill_s"] += time.perf_counter() - t0
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        out = [tok]
        t0 = time.perf_counter()
        for i in range(G - 1):
            pos = jnp.full((B, 1), PL + i, jnp.int32)
            logits, cache = decode(params, cache, tok, pos)
            tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            out.append(tok)
        jax.block_until_ready(tok)
        timings["decode_s"] += time.perf_counter() - t0
        timings["decoded"] += k * (G - 1)
        full = np.asarray(jnp.concatenate(out, axis=1))
        return [full[i] for i in range(k)]

    spec = ClusterSpec(
        n_workers=1,
        serve=ServeSpec(max_batch_size=B, max_wait_ms=args.max_wait_ms),
    )
    rng = np.random.default_rng(0)
    t_wall = time.perf_counter()
    with Session(cluster=spec, name=f"serve-{args.arch}") as session:
        server = session.serve(generate)
        server.attach(
            session.stream_consumer("requests"),
            session.stream_producer("responses"),
        )
        requests = session.stream_producer("requests")
        responses = session.stream_consumer("responses")

        for _ in range(n_req):
            prompt = rng.integers(0, cfg.vocab_size, (PL,)).astype(np.int32)
            requests.send(prompt)
        requests.close()  # EOS: the pump flushes and closes the reply topic

        outs, failures = {}, []
        for item in responses:
            if item.metadata.get("status") == "ok":
                outs[item.metadata["key"]] = item.value
            else:
                failures.append(f"{item.metadata.get('status')}: {item.value}")
        t_wall = time.perf_counter() - t_wall
        sstats = server.stats()
        hub = session.cluster.streams().stats()

    if len(outs) != n_req:
        raise RuntimeError(
            f"served {len(outs)}/{n_req} requests; first failures: {failures[:3]}"
        )
    tps = timings["decoded"] / timings["decode_s"] if timings["decode_s"] else 0.0
    print(f"served {n_req} reqs in {sstats['batches']} batches "
          f"(mean {sstats['mean_batch']:.2f}) | compile {compile_s:.1f}s "
          f"| prefill {timings['prefill_s']:.3f}s | decode {tps:,.1f} tok/s")
    print(f"latency p50/p99: {sstats['latency_p50_ms']:.1f}/"
          f"{sstats['latency_p99_ms']:.1f} ms | broker {hub['broker_bytes']:,}B "
          f"vs payload {hub['payload_bytes']:,}B")
    return {
        "outputs": outs,
        "compile_s": compile_s,
        "prefill_s": timings["prefill_s"],
        "decode_tok_s": tps,
        "requests": n_req,
        "wall_s": t_wall,
        "server": sstats,
        "stream": hub,
    }


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="serving batch width (ServeSpec.max_batch_size)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--max-wait-ms", type=float, default=5.0,
                    help="dynamic batcher window (ServeSpec.max_wait_ms)")
    ap.add_argument("--requests", type=int, default=0,
                    help="request count (default: 2x batch)")
    ap.add_argument("--run-dir", default="",
                    help="restore weights from this train run's store")
    return ap.parse_args(argv)


if __name__ == "__main__":
    serve(parse_args())
