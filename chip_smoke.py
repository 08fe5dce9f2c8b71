"""Smoke run of the system on a TPU, through the entry points users call.

    python chip_smoke.py               # one chip: device, kernels, serve, train
    python chip_smoke.py --four-chips  # four chips: sharded hymba-1.5b training

One chip, in order:

* **device** -- JAX's first device must be a TPU; anything else is an error.
* **kernels** -- flash attention at qwen2.5-3b geometry and the SSD scan at
  mamba2-130m geometry, compiled (a ``tpu_custom_call`` in the program) and
  checked against their ``ref.py`` oracles.
* **serve** -- ``repro.launch.serve.serve`` with full-width qwen2.5-3b:
  requests through ``Session``, the stream topics and ``ModelServer``.
* **train** -- ``repro.launch.train.train`` with full-width mamba2-130m:
  batches through ``ProxyPrefetcher`` and the store, an async checkpoint and
  the final blocking save, then ``restore()``.

With ``--four-chips`` only: full-width hymba-1.5b steps with the state
FSDP-sharded over four chips, then the same model cut to 4 layers trained on
one chip and on four from the same seed and batches; the losses must agree.

Each phase prints its own line.  The last line of a run that passed is one
JSON object naming the device; any failure raises and exits non-zero.  Runs
write only under ``artifacts/chip_smoke/``.
"""

from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from repro.launch.compile_cache import setup_compile_cache  # noqa: E402

RUN_ROOT = ROOT / "artifacts" / "chip_smoke"

#: Loss agreement of the 4-layer hymba-1.5b run on one chip and on four:
#: the same program split four ways sums its bf16 partial products and
#: gradients in another order, which moves each loss by well under 1e-2.
LOSS_ATOL = 2e-2


def phase_device() -> dict:
    import jax

    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise RuntimeError(f"no TPU: JAX's first device is {d.platform!r}")
    print(f"[device] platform={d.platform} kind={d.device_kind} count={len(devices)}",
          flush=True)
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def _check_close(name: str, out, ref, tol: float) -> float:
    import numpy as np

    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    if out.shape != ref.shape or not np.isfinite(out).all():
        raise RuntimeError(f"{name}: shape {out.shape} vs {ref.shape} or non-finite")
    np.testing.assert_allclose(out, ref, rtol=tol, atol=tol, err_msg=name)
    return float(np.max(np.abs(out - ref)))


def phase_kernels() -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_attention.ops import flash_attention_gqa
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.ssd_scan.ops import ssd_scan
    from repro.kernels.ssd_scan.ref import ssd_scan_ref

    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    bf16 = jnp.bfloat16

    # qwen2.5-3b attention: 16 query heads over 2 kv heads, head_dim 128.
    B, H, KV, S, hd = 2, 16, 2, 1024, 128
    q = jax.random.normal(keys[0], (B, H, S, hd), bf16)
    k = jax.random.normal(keys[1], (B, KV, S, hd), bf16)
    v = jax.random.normal(keys[2], (B, KV, S, hd), bf16)
    if "tpu_custom_call" not in flash_attention_gqa.lower(q, k, v).compile().as_text():
        raise RuntimeError("flash attention did not compile to a TPU kernel")
    with jax.default_matmul_precision("highest"):
        ref = attention_ref(q, k, v, causal=True)
    fa_err = _check_close("flash_attention", flash_attention_gqa(q, k, v), ref, 2e-2)

    # mamba2-130m SSD: 24 heads of P=64, d_state N=128, chunk 128.
    B, S, H, P, N = 2, 1024, 24, 64, 128
    x = (jax.random.normal(keys[3], (B, S, H, P)) * 0.5).astype(bf16)
    a = -jnp.abs(jax.random.normal(keys[4], (B, S, H))) * 0.3
    b = (jax.random.normal(keys[5], (B, S, H, N)) * 0.5).astype(bf16)
    c = (jax.random.normal(keys[6], (B, S, H, N)) * 0.5).astype(bf16)
    s0 = jax.random.normal(keys[7], (B, H, P, N)) * 0.2
    if "tpu_custom_call" not in ssd_scan.lower(x, a, b, c, s0, chunk=128).compile().as_text():
        raise RuntimeError("ssd_scan did not compile to a TPU kernel")
    y, sf = ssd_scan(x, a, b, c, s0, chunk=128)

    def bh(t):  # (B, S, H, ...) -> (B*H, S, ...)
        return jnp.moveaxis(t, 2, 1).reshape(B * H, S, *t.shape[3:])

    with jax.default_matmul_precision("highest"):
        yr, sr = ssd_scan_ref(bh(x), bh(a), bh(b), bh(c), s0.reshape(B * H, P, N))
    y_err = _check_close("ssd_scan y", bh(y), yr, 3e-2)
    s_err = _check_close("ssd_scan state", sf.reshape(B * H, P, N), sr, 3e-2)
    print(f"[kernels] flash_attention max|err|={fa_err:.3g} ssd_scan "
          f"y max|err|={y_err:.3g} state max|err|={s_err:.3g} (compiled)", flush=True)


def _peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


def phase_serve() -> None:
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.launch.serve import parse_args, serve, stage_line

    arch, gen, n_req = "qwen2.5-3b", 16, 8
    out = serve(parse_args([
        "--arch", arch, "--batch", "4", "--prompt-len", "128",
        "--gen", str(gen), "--requests", str(n_req),
    ]))
    vocab = get_config(arch).vocab_size
    outputs = list(out["outputs"].values())
    if len(outputs) != n_req:
        raise RuntimeError(f"serve answered {len(outputs)}/{n_req} requests")
    for toks in outputs:
        toks = np.asarray(toks)
        if toks.shape != (gen,) or toks.min() < 0 or toks.max() >= vocab:
            raise RuntimeError(f"bad generation: shape {toks.shape}, "
                               f"range [{toks.min()}, {toks.max()}]")
    print(f"[serve] {arch} full width: {n_req}/{n_req} requests ok | "
          f"compile {out['compile_s']:.1f}s | peak {_peak_bytes(jax.devices()[0])} B | "
          f"{stage_line(out['server'], out['stream']['topics'])}", flush=True)


def _run_dir(name: str) -> str:
    path = RUN_ROOT / name
    shutil.rmtree(path, ignore_errors=True)  # never resume an older run
    return str(path)


def phase_train() -> None:
    import jax
    import numpy as np

    from repro.launch.train import parse_args, train

    steps = 10
    out = train(parse_args([
        "--arch", "mamba2-130m", "--steps", str(steps), "--batch", "8",
        "--seq", "512", "--lr", "3e-3", "--remat", "dots", "--ckpt-every", "5",
        "--log-every", "1", "--run-dir", _run_dir("train"),
    ]))
    losses = [m["loss"] for m in out["log"]]
    if len(losses) != steps or not np.isfinite(losses).all():
        raise RuntimeError(f"losses: {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"loss did not fall: {losses}")
    ckpt = out["checkpoints"]
    if ckpt.restore(5) is None:
        raise RuntimeError("the async checkpoint of step 5 is missing")
    step, restored = ckpt.restore()
    if step != steps:
        raise RuntimeError(f"restored step {step}, expected {steps}")
    for got, want in zip(jax.tree.leaves(restored), jax.tree.leaves(out["state"])):
        if not np.array_equal(got, np.asarray(want)):
            raise RuntimeError("restored state differs from the trained state")
    print(f"[train] mamba2-130m full width: loss {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"over {steps} steps | compile {out['compile_s']:.1f}s | restored step "
          f"{step} | peak {_peak_bytes(jax.devices()[0])} B", flush=True)


def phase_four_chips() -> None:
    import jax

    from repro.configs import get_config
    from repro.launch.mesh import make_data_mesh
    from repro.launch.train import parse_args, train

    devices = jax.devices()
    if len(devices) != 4:
        raise RuntimeError(f"--four-chips needs 4 devices, found {len(devices)}")

    def args(name: str, steps: int):
        return parse_args([
            "--arch", "hymba-1.5b", "--steps", str(steps), "--batch", "8",
            "--seq", "512", "--remat", "dots", "--ckpt-every", "0",
            "--log-every", "1", "--run-dir", _run_dir(name),
        ])

    # Full width: the f32 state with Adam moments (~21 GiB) fits no one chip.
    out = train(args("hymba_full", 3))
    losses = [m["loss"] for m in out["log"]]
    shard_bytes = [0] * len(devices)
    for leaf in jax.tree.leaves(out["state"]):
        for shard in leaf.addressable_shards:
            shard_bytes[devices.index(shard.device)] += shard.data.nbytes
    total = sum(leaf.nbytes for leaf in jax.tree.leaves(out["state"]))
    peaks = [_peak_bytes(d) for d in devices]
    del out
    gc.collect()
    if max(shard_bytes) > total / 2:
        raise RuntimeError(f"state not spread: {shard_bytes} of {total} B")
    print(f"[four-chips] hymba-1.5b full width, 4-way FSDP: loss "
          f"{[round(x, 4) for x in losses]} | state {total} B, per device "
          f"{shard_bytes} | peak per device {peaks}", flush=True)

    # The same model cut to 4 layers (global attention on the first and the
    # last) on one chip and on four, from the same seed and batches.
    cut = get_config("hymba-1.5b").replace(num_layers=4, global_layers=(0, 3))
    runs = {}
    for n in (1, 4):
        res = train(args(f"hymba_cut_{n}", 5), cfg=cut,
                    mesh=make_data_mesh(devices[:n]))
        runs[n] = [m["loss"] for m in res["log"]]
        del res
        gc.collect()
    diff = max(abs(x - y) for x, y in zip(runs[1], runs[4]))
    if len(runs[1]) != 5 or len(runs[4]) != 5 or not diff <= LOSS_ATOL:
        raise RuntimeError(f"1 vs 4 chips: {runs[1]} vs {runs[4]}")
    print(f"[four-chips] hymba-1.5b 4 layers: 1 chip {[round(x, 4) for x in runs[1]]} "
          f"| 4 chips {[round(x, 4) for x in runs[4]]} | max|diff| {diff:.3g} "
          f"<= {LOSS_ATOL}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded training phase")
    opts = ap.parse_args(argv)
    setup_compile_cache()
    device = phase_device()
    if opts.four_chips:
        phase_four_chips()
    else:
        phase_kernels()
        phase_serve()
        gc.collect()  # drop the served weights before training
        phase_train()
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
