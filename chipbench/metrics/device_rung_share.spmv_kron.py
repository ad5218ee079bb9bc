"""Share of the window's launches that the jax rung served
(``LaunchReport.executor == "jax"``)."""


def read(run):
    return run.share(run.out.executors, "jax")
