"""The jax rung's chunk programs compile for a TPU v5e chip.

For each kernel of ``suite.USER_SIZES`` (user-sized launches of four
suite kernels), the benchmark's Kronecker SpMV at its scale
(``chipbench/configs/spmv_kron*``, its ragged loop compacted), and each
executable tier, the chunk program that
``jaxgen`` traces for that launch is compiled for device 0 of a
described ``v5e:2x2`` topology — no chip attached, nothing runs.  What
the TPU compiler refuses here fails here, not on the chip.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""
import importlib.util
import json
import os
import re
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.backends import jaxgen
from repro.core.runtime import compile_kernel
from repro.volt_bench.suite import BENCHES, USER_SIZES

CONFIGS = Path(__file__).resolve().parents[1] / "chipbench" / "configs"
KERNELS = tuple(USER_SIZES) + ("spmv_kron",)
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
            if name == "spmv_kron":
                fn, bufs, scalars, params = _spmv_kron()
            else:
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


def _load(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"test_tpu_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _spmv_kron():
    """The benchmark configuration's kernel and inputs at its size."""
    from repro.core.interp import LaunchParams
    cfg = json.loads((CONFIGS / "spmv_kron.json").read_text())
    with pytest.MonkeyPatch.context() as mp:
        mp.syspath_prepend(str(CONFIGS.parents[1]))
        ref = _load(CONFIGS / "spmv_kron_ref.py")
        kernel = _load(CONFIGS / "spmv_kron_kernel.py").KERNEL
    bufs, scalars, grid = ref.make(np.random.default_rng(0), cfg["size"])
    params = LaunchParams(grid=grid, local_size=cfg["block"], warp_size=32,
                          fuel=cfg["fuel"])
    return compile_kernel(kernel).fn, bufs, scalars, params


@pytest.fixture(scope="module")
def compiled(lowered):
    """(name, tier) -> the chunk program compiled for the described
    chip."""
    done = {}

    def get(name, tier):
        if (name, tier) not in done:
            done[name, tier] = lowered(name).compile(
                compiler_options=jaxgen._TIER_OPTIONS[tier])
        return done[name, tier]

    return get


@pytest.mark.parametrize("tier", TIERS)
@pytest.mark.parametrize("name", KERNELS)
def test_chunk_program_compiles_for_v5e(compiled, name, tier):
    exe = compiled(name, tier)
    mem = exe.memory_analysis()
    # one chunk's arguments, outputs and scratch fit the chip's 16 GB
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < 16 * 2**30


#: a gather in the compiled text: its result's element type and
#: dimensions, and the op name of its metadata
_GATHER = re.compile(r"= \w+\[([\d,]*)\]\S* gather\(.*?op_name=\"([^\"]*)\"")


@pytest.mark.parametrize("name", ["vecadd", "sgemm"])
def test_affine_accesses_compile_to_windows(compiled, name):
    """Every load of these kernels is a window candidate, so the only
    gathers of one element per lane of a chunk (R·W: 256 rows of 32,
    one workgroup of one warp a row) are the fallbacks, under
    ``jaxgen._GATHER_SCOPE``.  A window or broadcast that the compiler
    turned back into an element gather fails here."""
    rw = jaxgen._CHUNK_WGS * 32
    gathers = [(int(np.prod([int(d) for d in dims.split(",") if d])), op)
               for dims, op in _GATHER.findall(
                   compiled(name, "fast").as_text())]
    element = [op for n, op in gathers if n == rw]
    assert element and all(jaxgen._GATHER_SCOPE in op for op in element), \
        element
