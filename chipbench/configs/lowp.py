"""Rounding to bfloat16 in plain numpy, for the references' controls."""
import numpy as np


def to_bf16(a):
    """float32 values rounded to the nearest bfloat16 (ties to even),
    returned as float32.  Inputs here are finite."""
    u = np.ascontiguousarray(a, np.float32).view(np.uint32)
    r = (u + np.uint32(0x7FFF) + ((u >> 16) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return r.view(np.float32)
