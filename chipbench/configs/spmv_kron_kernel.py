"""CSR SpMV, one work item per row (the "CSR scalar" kernel of Bell and
Garland, SC'09), in the OpenCL dialect of VOLT's front end: the suite's
``spmv_csr``."""
from repro.core.frontends import opencl


@opencl.kernel
def spmv_csr(row_ptr: "ptr_i32 const", cols: "ptr_i32 const",
             vals: "ptr_f32 const", x: "ptr_f32 const", y: "ptr_f32",
             n: "i32 uniform"):
    gid = get_global_id(0)  # noqa: F821 - an intrinsic of the dialect
    if gid < n:
        acc = 0.0
        for e in range(row_ptr[gid], row_ptr[gid + 1]):
            acc += vals[e] * x[cols[e]]
        y[gid] = acc


KERNEL = spmv_csr
