"""spmv_kron's share of its roofline, %: the least time a launch's
operations and bytes (``work_of`` the seeded matrix) need at the chip's
peaks, over device busy time per launch the jax rung served."""
from chipbench.bench import peak_of


def read(run):
    served = sum(e == "jax" for e in run.out.executors)
    if run.trace is None or not served or run.trace["busy_s"] <= 0:
        return None
    peak = peak_of(run.device_kind)
    flops, nbytes = run.cell.ref.work_of(run.driver.base)
    least = max(flops / peak["flops_per_s"],
                nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / (run.trace["busy_s"] / served)
