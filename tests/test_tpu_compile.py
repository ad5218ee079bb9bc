"""The jax rung's chunk programs compile for a TPU v5e chip.

For each kernel of ``suite.USER_SIZES`` (the sizes ``chip_smoke.py``
launches on the chip) and each executable tier, the chunk program that
``jaxgen`` traces for that launch is compiled for device 0 of a
described ``v5e:2x2`` topology — no chip attached, nothing runs.  What
the TPU compiler refuses here fails here, not on the chip.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""
import os

import jax
import numpy as np
import pytest

from repro.core.backends import jaxgen
from repro.core.runtime import compile_kernel
from repro.volt_bench.suite import BENCHES, USER_SIZES

KERNELS = tuple(USER_SIZES)
TIERS = tuple(jaxgen._TIER_OPTIONS)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def lowered(one_chip):
    """name -> the chunk program lowered for the described chip.  The
    persistent compilation cache is off meanwhile: an entry compiled for
    a chip that is not attached could not be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    done = {}

    def get(name):
        if name not in done:
            b = BENCHES[name]
            bufs, scalars, params = b.make(np.random.default_rng(0),
                                           **USER_SIZES[name])
            fn = compile_kernel(b.handle).fn
            rec = jaxgen._trace(fn, params, bufs, scalars,
                                jaxgen._chunk_width(params))
            args = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=one_chip),
                rec.abstract)
            done[name] = rec.jitted.lower(*args)
        return done[name]

    yield get
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("tier", TIERS)
@pytest.mark.parametrize("name", KERNELS)
def test_chunk_program_compiles_for_v5e(lowered, name, tier):
    exe = lowered(name).compile(
        compiler_options=jaxgen._TIER_OPTIONS[tier])
    mem = exe.memory_analysis()
    # one chunk's arguments, outputs and scratch fit the chip's 16 GB
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < 16 * 2**30
