"""Executions of device programs in the traced window, per launch."""


def read(run):
    if run.trace is None or not run.launches:
        return None
    return run.trace["programs"] / run.launches
