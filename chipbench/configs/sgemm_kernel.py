"""The naive sgemm kernel in the OpenCL dialect of VOLT's front end."""
from repro.core.frontends import opencl


@opencl.kernel
def sgemm(a: "ptr_f32 const", b: "ptr_f32 const", c: "ptr_f32",
          m: "i32 uniform", n: "i32 uniform", k: "i32 uniform"):
    gid = get_global_id(0)  # noqa: F821 - an intrinsic of the dialect
    if gid < m * n:
        row = gid // n
        col = gid - row * n
        acc = 0.0
        for i in range(k):
            acc += a[row * k + i] * b[i * n + col]
        c[gid] = acc


KERNEL = sgemm
