"""Every cell, run end to end on the CPU at its rehearsal size: a sound
run comes out correct, and the control (the reference from bfloat16
inputs in the program's place) and each planted fault do not.

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests
"""
import json

import pytest

from chipbench import bench, faults, spec

CELLS = [w["name"] for w in json.loads(
    (spec.ROOT / "BENCHMARK.json").read_text())["workloads"]]
SEED = 2**31 + 11        # above 32 signed bits, as the benchmark's are


@pytest.fixture(scope="module", autouse=True)
def env():
    bench.prepare_env()


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    r = bench.run(cell, SEED, 0.3, False, rehearse=True)
    assert r["correct"], r["compared"]
    assert r["compared"]["max_ulp_gap"]["value"] == 0
    assert r["metrics"] == {}           # a CPU run reports no metric


@pytest.mark.parametrize("kind", faults.KINDS)
@pytest.mark.parametrize("cell", CELLS)
def test_control_and_faults_are_not_correct(cell, kind):
    planted = faults.planted(kind, spec.load_cell(cell))
    r = bench.run(cell, SEED + 1, 0.3, False, rehearse=True,
                  window_hook=planted)
    assert not r["correct"], r["compared"]
