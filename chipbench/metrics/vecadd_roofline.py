"""vecadd's share of its roofline, %: the least time its operations
and bytes need at the chip's peaks, over device busy time per launch."""


def read(run):
    return run.roofline()
