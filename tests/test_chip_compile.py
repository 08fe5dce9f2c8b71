"""The Pallas kernels compile for a described TPU v5e chip at real widths.

Interpret mode runs the kernel bodies on the CPU but never meets the chip's
compiler, which refuses what the interpreter accepts (blocks off the (8, 128)
tiling, primitives Mosaic cannot lower).  These tests compile each kernel for
one chip of a ``v5e:2x2`` topology that is described, not attached, and check
that the program holds the kernel as a ``tpu_custom_call``.

The topology is described only inside the module fixture: loading the TPU
library at import would make pytest-xdist workers collect different tests.
"""

from __future__ import annotations

import json
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.kernels.fingerprint.ops import fingerprint
from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.flash_attention.ops import flash_attention_gqa
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.models import transformer as tx
from repro.models.common import ModelConfig

PHI4 = Path(__file__).resolve().parents[1] / "bench" / "configs" / "phi4-mini-3.8b.json"


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it.
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _hlo(fn, shapes, sharding, **static) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return fn.lower(*args, **static).compile().as_text()


def test_flash_attention_compiles_at_qwen2_5_3b_widths(one_chip):
    # qwen2.5-3b: 16 query heads over 2 kv heads (GQA 8:1), head_dim 128.
    B, H, KV, S, hd = 2, 16, 2, 512, 128
    hlo = _hlo(
        flash_attention_gqa,
        [((B, H, S, hd), jnp.bfloat16), ((B, KV, S, hd), jnp.bfloat16),
         ((B, KV, S, hd), jnp.bfloat16)],
        one_chip, causal=True, interpret=False,
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize(
    "H,N",
    [(24, 128), (25, 16)],
    ids=["mamba2-130m", "hymba-1.5b"],
)
def test_ssd_scan_compiles_at_model_widths(one_chip, H, N):
    # head_dim P=64 and chunk Q=128 in both models; N is d_state.
    B, S, P = 2, 512, 64
    hlo = _hlo(
        ssd_scan,
        [((B, S, H, P), jnp.bfloat16), ((B, S, H), jnp.float32),
         ((B, S, H, N), jnp.bfloat16), ((B, S, H, N), jnp.bfloat16),
         ((B, H, P, N), jnp.float32)],
        one_chip, chunk=128, interpret=False,
    )
    assert "tpu_custom_call" in hlo


def test_fingerprint_compiles_on_a_few_mib(one_chip):
    hlo = _hlo(fingerprint, [((4 << 20,), jnp.uint8)], one_chip, interpret=False)
    assert "tpu_custom_call" in hlo


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The kernels in the mode the chip runs them in (the host here is a CPU,
    whose default backend would pick the interpreter); traces taken in the
    other mode are dropped on both sides."""
    jax.clear_caches()
    monkeypatch.setattr(flash_ops, "interpret_mode", lambda: False)
    yield
    jax.clear_caches()


def test_served_prefill_runs_flash_kernel_at_phi4_mini_widths(one_chip, compiled_kernels):
    """The benchmark's served phi4-mini-3.8b batch (16 prompts of 512 tokens,
    641 cache slots) prefills in the flash kernel, scoped ``attn/flash``:
    no f32 score matrix over the cache and no scatter into it.  Its decode
    step holds no kernel."""
    model = json.loads(PHI4.read_text())["model"]
    cfg = ModelConfig(**{**model, "param_dtype": jnp.bfloat16, "compute_dtype": jnp.bfloat16})
    B, S, slots = 16, 512, 641
    mesh = Mesh(np.array(list(one_chip.device_set)).reshape(1, 1), ("data", "model"))
    ctx = tx.RunCtx(mesh=mesh, dp_axes=("data",), ep_axis="model", decode=True)
    on_chip = NamedSharding(mesh, PartitionSpec())

    def shapes(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on_chip), tree
        )

    params = shapes(jax.eval_shape(lambda k: tx.init_params(cfg, k), jax.random.PRNGKey(0)))
    cache = shapes(jax.eval_shape(lambda: tx.init_cache(cfg, B, slots)))
    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=on_chip)
    one = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=on_chip)

    prefill = jax.jit(lambda p, t, c: tx.prefill(cfg, p, t, c, ctx))
    hlo = prefill.lower(params, tokens, cache).compile().as_text()
    kernels = [line for line in hlo.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert kernels and all("/attn/flash/" in line for line in kernels)
    assert f"f32[{B},{S},8,3,{slots}]" not in hlo
    assert not re.search(r"\bscatter\(|kv_write/scatter", hlo)

    decode = jax.jit(lambda p, c, t, pos: tx.decode_step(cfg, p, c, t, pos, ctx))
    assert "tpu_custom_call" not in decode.lower(params, cache, one, one).compile().as_text()
