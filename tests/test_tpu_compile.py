"""Compile the served Pallas kernels for a TPU v5e, without the chip.

The TPU compiler ships with jaxlib and compiles for a chip that is only
described (``jax.experimental.topologies``), so these tests catch what
interpret mode cannot: block shapes Mosaic refuses, unsupported in-kernel
ops, VMEM overruns.  Every kernel in ``chip_smoke.kernel_cases`` — each one
the served path can select — is compiled at bitnet-0.73b's published
widths with ``interpret=False``, and the program must hold the Mosaic
custom call.  Nothing runs, so nothing here is a result or a time.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and under pytest-xdist
every worker imports every test file.
"""

from __future__ import annotations

import functools
import os

import jax
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke
from repro.configs import PAPER_ARCH, get_config

CASES = chip_smoke.kernel_cases(get_config(PAPER_ARCH))


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an ahead-of-time compile for a described chip cannot be read back
    # from the persistent cache, so keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def test_cases_cover_the_served_kernels():
    assert {c.name for c in CASES} == {
        "tlmm_qkv", "tlmm_o", "tlmm_gateup", "tlmm_down",
        "decode_attention", "decode_attention_paged",
        "decode_attention_paged_quant", "flash_prefill",
        "flash_chunk_prefill", "flash_chunk_prefill_paged"}


@pytest.mark.parametrize("case", CASES, ids=[c.name for c in CASES])
def test_kernel_compiles_for_v5e(case, one_chip):
    shapes = jax.eval_shape(case.make_args, jax.random.PRNGKey(0))
    args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
            for s in shapes]
    fn = jax.jit(functools.partial(case.kernel, interpret=False))
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
