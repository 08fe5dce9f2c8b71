"""Pallas TPU kernels for the framework's compute hot-spots.

The paper itself is middleware (no kernel contribution); these kernels
serve the perf-critical substrate layers identified by the roofline:

* ``flash_attention`` -- GQA flash attention (serving/prefill hot-spot)
* ``ssd_scan``        -- Mamba-2 SSD chunked scan (SSM/hybrid archs)
* ``fingerprint``     -- content-addressed tokens for proxy/task keys
                          (the paper's key-hashing, as a bandwidth kernel)

Each kernel ships ``kernel.py`` (pl.pallas_call + BlockSpec VMEM tiling),
``ops.py`` (jit'd public wrapper), and ``ref.py`` (pure-jnp oracle used by
the allclose test sweeps).  Every wrapper takes its Pallas mode from
:func:`interpret_mode` unless the caller passes ``interpret`` itself.
"""

import jax


def interpret_mode() -> bool:
    """Pallas mode for this process's default backend.

    The CPU runs the kernel body in the Pallas interpreter (the tests); the
    TPU runs the compiled kernel.  Any other platform is an error, never a
    quiet fall back to the interpreter.
    """
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(f"no Pallas kernel mode for platform {platform!r}")
