"""Quickstart: compile an OpenCL-style kernel with VOLT, inspect the
divergence-managed IR + Vortex assembly, and execute it three ways
(SIMT interpreter, JAX backend, Pallas).

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax.numpy as jnp

from repro.core import interp
from repro.core.backends.asm import emit_asm
from repro.core.backends.jax_backend import compile_jax
from repro.core.frontends import opencl
from repro.core.passes.pipeline import PassConfig, run_pipeline
from repro.core.runtime import use_jax_compile_cache


@opencl.kernel
def smooth(x: "ptr_f32 const", y: "ptr_f32", n: "i32 uniform"):
    gid = get_global_id(0)
    if gid < n:
        acc = x[gid]
        w = 1.0
        if gid > 0:
            acc += x[gid - 1]
            w += 1.0
        if gid < n - 1:
            acc += x[gid + 1]
            w += 1.0
        y[gid] = acc / w


def main() -> None:
    use_jax_compile_cache()
    # 1. front-end + middle-end (uniformity, structurize, Algorithm 2)
    module = smooth.build(None)
    compiled = run_pipeline(module, "smooth",
                            PassConfig(uni_hw=True, uni_ann=True))
    print("=== divergence-managed VIR ===")
    print(compiled.fn.dump())
    print("\n=== Vortex-flavored assembly ===")
    print(emit_asm(compiled.fn))

    # 2. execute on the warp interpreter (SimX stand-in)
    rng = np.random.default_rng(0)
    n = 120
    x = rng.standard_normal(128).astype(np.float32)
    bufs = {"x": x.copy(), "y": np.zeros(128, np.float32)}
    params = interp.LaunchParams(grid=4, local_size=32)
    stats = interp.launch(compiled.fn, bufs, params, scalar_args={"n": n})
    print(f"\ninterpreter: {stats.instrs} warp-instructions, "
          f"{stats.mem_requests} memory line requests, "
          f"IPDOM depth {stats.max_ipdom_depth}")

    # 3. the same kernel lowered to vectorized JAX (the TPU back-end)
    jk = compile_jax(compiled.fn, params, module)
    out = jk.fn({"x": jnp.array(x), "y": jnp.zeros(128, jnp.float32)},
                {"n": jnp.int32(n)})
    assert np.allclose(np.asarray(out["y"]), bufs["y"], atol=1e-5)
    print("JAX backend matches the interpreter: OK")


if __name__ == "__main__":
    main()
