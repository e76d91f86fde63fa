"""Pallas TPU kernels for the paper's compute hot-spots.

Each kernel package contains:
  kernel.py — pl.pallas_call body with explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (padding, dtype plumbing, interpret switch)
  ref.py    — pure-jnp oracle used by tests and by the XLA fallback paths

Off the TPU (the CPU test runs) kernels run with interpret=True; on TPU the
same code lowers to Mosaic.  ``default_interpret()`` picks automatically.
Interpret mode accepts block shapes Mosaic refuses, so
``tests/test_tpu_compile.py`` compiles every served kernel for a described
v5e as well.
"""

import jax


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"
