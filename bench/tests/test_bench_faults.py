"""The comparison with the reference catches every fault planted under
the timed path (``exchange_dropped`` is also each cell's control); sound
runs pass it.  The harness runs
here on the CPU at sizes a test holds (the chip's runs use the cells'
own sizes, through ``bench/control.py``)."""

import contextlib
import json
import os
import subprocess
import sys

import pytest

from bench import faults, harness

SEED = 2**31 + 12345
SMALL_DAG = {"config": {"nodes": 20_000}}
SMALL_KNAPSACK = {"config": {"instances": {"n": 14, "R": 1000, "h": 50,
                                           "H": 100}},
                  "traffic": {"pool_size": 3}}


def run(cell, overrides, plant=None, seconds=0.5):
    ctx = faults.PLANTS[plant]() if plant else contextlib.nullcontext()
    with ctx:
        return harness.run_cell(cell, SEED, seconds, False,
                                require_tpu=False, overrides=overrides)


@pytest.mark.parametrize("plant", [None, "state_unchanged", "half_batch",
                                   "exchange_dropped", "answer_altered"])
def test_drain_cell_catches_faults(plant):
    result = run("fig9-dag.drain", SMALL_DAG, plant)
    assert result["correct"] is (plant is None), result["checks"]
    assert result["attempted"] >= 1
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("plant", [None, "state_unchanged", "half_batch",
                                   "exchange_dropped", "answer_altered"])
def test_solve_cell_catches_faults(plant):
    result = run("dd-knapsack.sc", SMALL_KNAPSACK, plant, seconds=0.1)
    assert result["correct"] is (plant is None), result["checks"]
    assert result["attempted"] % 3 == 0  # whole passes over the pool


def test_mesh_cell_catches_faults():
    """The four-lane mesh cell, on four virtual CPU devices in a child
    process (the device count is fixed when JAX starts)."""
    code = (
        "import json, sys\n"
        "from bench.tests.test_bench_faults import run, SMALL_DAG\n"
        "out = {p: run('fig9-dag.mesh4', SMALL_DAG, p)['correct']\n"
        "       for p in (None, 'state_unchanged', 'half_batch',\n"
        "                 'exchange_dropped', 'answer_altered')}\n"
        "print(json.dumps({str(k): v for k, v in out.items()}))\n")
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([os.path.join(root, "src"), root]))
    proc = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got == {"None": True, "state_unchanged": False,
                   "half_batch": False, "exchange_dropped": False,
                   "answer_altered": False}
