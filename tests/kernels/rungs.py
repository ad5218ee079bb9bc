"""The jax column of the executor tables.

``launch`` is ``interp.launch``, except with ``rung="jax"`` (the table
entry ``JAX``): then it runs the jax rung as the runtime's chain does,
``runtime.interp_launch(..., rung="jax")`` (the rung's entry, and the
grid rung where it declines), and the grid rung where the rung raises
an ``EngineFault``.

The small-launch router is held off (``jaxgen._ROUTE_MARGIN`` 0): the
tables check the jitted program against the oracle, so a certified
shape class keeps running it however fast the grid walk is.
"""
from repro.core import interp, runtime
from repro.core.faults import EngineFault

#: the jax column's entry in an executor table
JAX = {"rung": "jax"}


def launch(fn, buffers, params, scalar_args=None, globals_mem=None, *,
           rung=None, **kw):
    if rung is None:
        return interp.launch(fn, buffers, params, scalar_args,
                             globals_mem, **kw)
    from repro.core.backends import jaxgen
    margin, jaxgen._ROUTE_MARGIN = jaxgen._ROUTE_MARGIN, 0
    try:
        return runtime.interp_launch(fn, buffers, params, scalar_args,
                                     rung=rung, globals_mem=globals_mem,
                                     **kw)
    except EngineFault:
        return interp.launch(fn, buffers, params, scalar_args,
                             globals_mem, **kw)
    finally:
        jaxgen._ROUTE_MARGIN = margin
