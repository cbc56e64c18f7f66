"""BENCHMARK.json and the files it names: everything is found by name,
and a new cell, traffic or metric needs new files and entries only."""

import json
import os
import re
import shutil
import time

import pytest

from bench import harness, jobs

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SPEC = harness.load_spec()


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_units_and_keys():
    for entry in SPEC["configs"]:
        assert set(entry) == {"name", "source", "file", "reduced", "why"}
        assert entry["file"].startswith("bench/")
    for cell in SPEC["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    for metric in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
        assert metric["better"] in ("lower", "higher")
    for metric in SPEC["end_to_end"]:
        assert 0.01 <= metric["bound"] <= 0.25
        assert metric["source"] in ("host_clock", "device_trace")
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for metric in SPEC["per_layer"]:
        assert metric["moves"] in e2e
    four = [c for c in SPEC["workloads"] if c["chips"] == 4]
    assert len(four) <= 1


@pytest.mark.parametrize("cell", [c["name"] for c in SPEC["workloads"]])
def test_every_cell_resolves(cell):
    found = harness.resolve(SPEC, cell)
    assert os.path.isfile(jobs.driver_path(found["traffic"]["kind"]))
    assert found["layout"]["lanes"] >= 1
    reported = {m["name"] for m in found["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2
    assert found["per_layer"], f"{cell} reports no per-layer metric"
    for metric in found["per_layer"]:
        assert metric["moves"] in reported
        assert callable(harness.load_reader(metric["name"]))
    jobs.make(found["config"], found["traffic"], found["layout"], seed=1)


def test_new_cell_needs_only_new_files(tmp_path):
    """A later change adds a configuration, a traffic mix and a metric as
    new files plus entries in BENCHMARK.json, and edits nothing else."""
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH_DIR, root / "bench")
    spec = json.loads(json.dumps(SPEC))
    config = json.loads((root / "bench/configs/fig9-dag.json").read_text())
    config.update(name="wide-dag", fanout=8)
    (root / "bench/configs/wide-dag.json").write_text(json.dumps(config))
    (root / "bench/traffic/burst.json").write_text(json.dumps(
        {"kind": "dag_drain", "max_rounds_factor": 4, "trace_seconds": 2}))
    (root / "bench/layer_metrics/rounds.burst.py").write_text(
        "def read(ctx):\n    return ctx['counters']['rounds']\n")
    spec["configs"].append({"name": "wide-dag", "source": "x",
                            "file": "bench/configs/wide-dag.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "wide-dag.burst", "config": "wide-dag",
                              "traffic": "burst", "chips": 1, "why": "x"})
    spec["end_to_end"][0]["workloads"].append("wide-dag.burst")
    spec["per_layer"].append({"name": "rounds.burst", "unit": "rounds",
                              "better": "lower", "source": "program_counter",
                              "layer": "superstep master",
                              "moves": "nodes_per_s",
                              "workloads": ["wide-dag.burst"]})
    found = harness.resolve(spec, "wide-dag.burst", root=str(root))
    assert found["config"]["fanout"] == 8
    assert [m["name"] for m in found["per_layer"]] == ["rounds.burst"]
    reader = harness.load_reader("rounds.burst", root=str(root))
    assert reader({"counters": {"rounds": 7}}) == 7
    job = jobs.make(found["config"], found["traffic"], found["layout"], 3,
                    bench_dir=str(root / "bench"))
    assert job.fanout == 8


TOY_DRIVER = """
from bench.jobs import annotate


class Driver:
    def __init__(self, config, traffic, layout, seed):
        self.n, self.seed = int(traffic["jobs"]), seed
        self.done = []

    def setup(self):
        pass

    def window(self, seconds, clock, traced=False):
        t0 = clock()
        with annotate("window"):
            self.done = [self.seed + i for i in range(self.n)]
        return {"e2e": {"jobs_per_s": self.n / max(clock() - t0, 1e-9)},
                "elapsed_s": clock() - t0, "counters": {"jobs": self.n}}

    def finish(self):
        pass

    def attempted(self):
        return len(self.done)

    def kernel_rows(self):
        return {}

    def checks(self):
        wrong = sum(x != self.seed + i for i, x in enumerate(self.done))
        return [("wrong_jobs", wrong, 0)], wrong
"""


def test_new_kind_needs_only_new_files(tmp_path):
    """A later change adds a new kind of job (its driver), with its
    configuration and traffic, as new files only."""
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH_DIR, root / "bench")
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    (root / "bench/drivers/toy_count.py").write_text(TOY_DRIVER)
    (root / "bench/traffic/toy.json").write_text(json.dumps(
        {"kind": "toy_count", "jobs": 5, "trace_seconds": 1}))
    (root / "bench/configs/toy.json").write_text(json.dumps(
        {"name": "toy", "layout": {"1": {"lanes": 1, "execution": "vmap"}}}))
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "toy", "source": "x",
                            "file": "bench/configs/toy.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "toy.count", "config": "toy",
                              "traffic": "toy", "chips": 1, "why": "x"})
    found = harness.resolve(spec, "toy.count", root=str(root))
    job = jobs.make(found["config"], found["traffic"], found["layout"], 11,
                    bench_dir=str(root / "bench"))
    job.setup()
    out = job.window(0.1, time.perf_counter)
    job.finish()
    assert out["counters"] == {"jobs": 5} and job.attempted() == 5
    assert job.checks() == ([("wrong_jobs", 0, 0)], 0)
    assert all(p.read_bytes() == data for p, data in before.items())


@pytest.mark.parametrize("layout,chips,ok", [
    ({"lanes": 8, "execution": "vmap"}, 1, True),
    ({"lanes": 4, "execution": "mesh"}, 4, True),
    ({"lanes": 8, "execution": "vmap"}, 4, False),
    ({"lanes": 8, "execution": "mesh"}, 4, False),
    ({"lanes": 4, "execution": "shard"}, 4, False),
])
def test_layout_is_refused_unless_it_fills_the_chips(layout, chips, ok):
    if ok:
        harness.check_layout(layout, chips)
    else:
        with pytest.raises(ValueError):
            harness.check_layout(layout, chips)


def test_unknown_kind_is_refused():
    with pytest.raises(ValueError, match="no driver"):
        jobs.make({}, {"kind": "no_such_kind"}, {}, 1)
