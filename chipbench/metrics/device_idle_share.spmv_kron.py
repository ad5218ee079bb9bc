"""1 - (union of device operation intervals) / window, from the
profiler trace."""


def read(run):
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return 1.0 - run.trace["busy_s"] / run.trace["window_s"]
