"""The measurement path refuses to run without a TPU, and a checkout
that holds only the benchmark cannot run at all; neither prints a
result."""

import os
import shutil
import subprocess
import sys

import pytest

from bench import harness


def test_refuses_the_cpu(capsys):
    rc = harness.main(["--workload", "fig9-dag.drain", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert out == ""
    assert "needs a TPU" in err


def test_refuses_too_few_chips():
    with pytest.raises(harness.NoAccelerator):
        harness.device_info(4, require_tpu=False)


def test_benchmark_alone_does_not_run(tmp_path):
    shutil.copytree(harness.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.SPEC_FILE, tmp_path / "BENCHMARK.json")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fig9-dag.drain",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
