"""The launch path's host spans (``volt.*``, core/spans.py) and the jax
rung's transfer counters.

  * under ``jax.profiler`` every launch step shows up as a named span,
    nested by call: the chain's own steps inside ``volt.launch``, the
    jax rung's host steps inside the launch or its certification run;
    one dispatch span per launch, none per chunk;
  * the spans change nothing: buffers and ``ExecStats`` are
    bit-identical with the profiler on and off;
  * ``upload_bytes`` and ``download_bytes`` count the bound buffers of
    each launch the jitted program served, so over ``engaged`` they are
    one launch's traffic;
  * the numpy chain stays JAX-free: launching with ``jax=False`` imports
    no JAX.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import faults
from repro.core.backends import jaxgen
from repro.core.passes.pipeline import ABLATION_LADDER, run_pipeline
from repro.core.runtime import Runtime
from repro.volt_bench import BENCHES

SRC = Path(__file__).resolve().parents[1] / "src"

#: 2^14 elements: 512 workgroups of 32, two chunk programs a launch
SIZE = 2**14

#: the span each span is called from (the innermost ``volt.*`` span
#: around it on the same thread)
PARENTS = {
    "volt.launch": {None},
    "volt.launch.snapshot": {"volt.launch"},
    "volt.launch.rollback": {"volt.launch"},
    "volt.jax.prepare": {"volt.launch"},
    "volt.jax.certify": {"volt.launch"},
    "volt.jax.apply": {"volt.launch"},
    **{f"volt.jax.{step}": {"volt.launch", "volt.jax.certify"}
       for step in ("upload", "dispatch", "sync", "download")},
}


@pytest.fixture(autouse=True)
def _isolated(monkeypatch, tmp_path):
    monkeypatch.setenv("VOLT_CACHE_DIR", str(tmp_path / "volt"))
    # the small-launch router compares two host timings and may send a
    # launch this small to the grid rung on a busy host; every launch
    # here has to take the jax rung
    monkeypatch.setattr(jaxgen, "_ROUTE_MARGIN", 0.0)
    jaxgen.reset_jax_telemetry()


def _vecadd():
    b = BENCHES["vecadd"]
    fn = run_pipeline(b.handle.build(None), b.handle.name,
                      ABLATION_LADDER[-1]).fn
    bufs, scalars, params = b.make(np.random.default_rng(0), size=SIZE)
    return fn, bufs, scalars, params


def _launch(rt, fn, bufs, scalars, params):
    return rt.launch(fn, grid=params.grid, block=params.local_size,
                     scalar_args=scalars, buffers=bufs)


def _traced(tmp_path, work) -> list:
    """Run ``work()`` under the profiler; the ``volt.*`` host events of
    the trace as ``(name, parent, start_ns, end_ns)``, ``parent`` the
    innermost ``volt.*`` event around it on its thread."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    tdir = tmp_path / "trace"
    jax.profiler.start_trace(str(tdir), profiler_options=opts)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    (path,) = tdir.rglob("*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events if e.name.startswith("volt.")]
            for name, s, e in evs:
                around = [o for o in evs if o[1] <= s and e <= o[2]
                          and o[2] - o[1] > e - s]
                parent = min(around, key=lambda o: o[2] - o[1],
                             default=(None,))[0]
                out.append((name, parent, s, e))
    return out


def test_spans_nest_by_call(tmp_path):
    fn, bufs, scalars, params = _vecadd()
    rt = Runtime(jax=True)

    def work():
        _launch(rt, fn, bufs, scalars, params)       # certification run
        for _ in range(3):
            _launch(rt, fn, bufs, scalars, params)   # certified primary
        with faults.inject("jax.exec", prob=1.0, seed=0):
            _launch(rt, fn, bufs, scalars, params)   # demoted, rolled back

    evs = _traced(tmp_path, work)
    assert rt.last_report.rolled_back == 1
    assert jaxgen.JAX_TELEMETRY["engaged"] == 3
    names = [n for n, *_ in evs]
    assert set(names) == set(PARENTS)
    for name, parent, _s, _e in evs:
        assert parent in PARENTS[name], (name, parent)
    count = {n: names.count(n) for n in PARENTS}
    assert count["volt.launch"] == 5
    assert count["volt.jax.prepare"] == 5
    assert count["volt.jax.certify"] == 1
    assert count["volt.launch.rollback"] == 1
    # one span around the chunk loop, never one per chunk: the cert run
    # and the three primaries dispatch, the faulted launch raises inside
    assert count["volt.jax.dispatch"] == 5
    assert count["volt.jax.apply"] == count["volt.jax.download"] - 1 == 3


def test_spans_change_no_result(tmp_path):
    fn, bufs, scalars, params = _vecadd()
    rt = Runtime(jax=True)
    _launch(rt, fn, bufs, scalars, params)           # certification run

    def run():
        got = {k: v.copy() for k, v in bufs.items()}
        got["z"][:] = 0
        stats = _launch(rt, fn, got, scalars, params)
        assert rt.last_report.executor == "jax"
        return got, dataclasses.asdict(stats)

    off = run()
    on = []
    assert _traced(tmp_path, lambda: on.append(run()))
    (on,) = on
    for k in bufs:
        assert off[0][k].tobytes() == on[0][k].tobytes()
    assert off[1] == on[1]


def test_transfer_counters_are_one_launch_of_bound_buffers():
    fn, bufs, scalars, params = _vecadd()
    rt = Runtime(jax=True)
    t = jaxgen.JAX_TELEMETRY
    _launch(rt, fn, bufs, scalars, params)           # certification run
    assert t["engaged"] == t["upload_bytes"] == t["download_bytes"] == 0
    for _ in range(3):
        _launch(rt, fn, bufs, scalars, params)
    bound = sum(a.nbytes for a in bufs.values())
    assert bound == 3 * SIZE * 4
    assert t["engaged"] == 3
    assert t["upload_bytes"] / t["engaged"] == bound
    assert t["download_bytes"] / t["engaged"] == bound


def test_numpy_chain_imports_no_jax(tmp_path):
    script = textwrap.dedent(f"""
        import sys
        import numpy as np
        from repro.core.runtime import Runtime
        from repro.core.passes.pipeline import ABLATION_LADDER, run_pipeline
        from repro.volt_bench import BENCHES
        b = BENCHES["vecadd"]
        fn = run_pipeline(b.handle.build(None), b.handle.name,
                          ABLATION_LADDER[-1]).fn
        bufs, scalars, p = b.make(np.random.default_rng(0), size={SIZE})
        Runtime(jax=False).launch(fn, grid=p.grid, block=p.local_size,
                                  scalar_args=scalars, buffers=bufs)
        np.testing.assert_array_equal(bufs["z"], bufs["x"] + bufs["y"])
        print(sorted(m for m in sys.modules
                     if m == "jax" or m.startswith("jax.")))
    """)
    env = dict(os.environ, PYTHONPATH=str(SRC),
               VOLT_CACHE_DIR=str(tmp_path / "volt"))
    env.pop("VOLT_JAX", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == "[]"
