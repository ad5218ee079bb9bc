"""The vecadd kernel in the OpenCL dialect of VOLT's front end."""
from repro.core.frontends import opencl


@opencl.kernel
def vecadd(x: "ptr_f32 const", y: "ptr_f32 const", z: "ptr_f32",
           n: "i32 uniform"):
    gid = get_global_id(0)  # noqa: F821 - an intrinsic of the dialect
    if gid < n:
        z[gid] = x[gid] + y[gid]


KERNEL = vecadd
