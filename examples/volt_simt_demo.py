"""Paper-reproduction demo: the divergence-optimization ablation on three
benchmarks, printing the Fig 7/8-style deltas, plus the same kernel
executed as a Pallas TPU kernel (interpret mode).

    PYTHONPATH=src python examples/volt_simt_demo.py
"""
import numpy as np
import jax.numpy as jnp

from repro.core import interp
from repro.core.passes.pipeline import ABLATION_LADDER, run_pipeline
from repro.core.runtime import use_jax_compile_cache
from repro.core.simx import CycleModel
from repro.volt_bench import BENCHES


def main() -> None:
    use_jax_compile_cache()
    model = CycleModel()
    for name in ("srad_flag", "transpose", "pathfinder"):
        b = BENCHES[name]
        rng = np.random.default_rng(7)
        bufs0, scalars, params = b.make(rng)
        print(f"\n=== {name} ===")
        base = None
        for cfg in ABLATION_LADDER:
            mod = b.handle.build(None)
            ck = run_pipeline(mod, b.handle.name, cfg)
            bufs = {k: v.copy() for k, v in bufs0.items()}
            st = interp.launch(ck.fn, bufs, params, scalar_args=scalars)
            cyc = model.cycles(st)
            if base is None:
                base = (st.instrs, cyc)
            print(f"  {cfg.label:28s} instrs={st.instrs:6d} "
                  f"(x{base[0]/st.instrs:5.3f})  cycles={cyc:9.0f} "
                  f"(x{base[1]/cyc:5.3f})")

    # Pallas execution of a tile-friendly kernel
    from repro.kernels.simt_exec.ops import volt_pallas_run
    sx = BENCHES["saxpy"]
    bufs0, scalars, params = sx.make(np.random.default_rng(3))
    out = volt_pallas_run(
        sx.handle, {k: jnp.array(v) for k, v in bufs0.items()}, params,
        {k: np.asarray(v) for k, v in scalars.items()})
    expect = sx.ref(bufs0, scalars)
    assert np.allclose(np.asarray(out["y"]), expect["y"], atol=1e-5)
    print("\nsaxpy as a Pallas TPU kernel (interpret mode): OK")


if __name__ == "__main__":
    main()
