"""``--rehearse`` of every cell in BENCHMARK.json ends in a well-formed
last line whose device says cpu and which holds no device number; and a
run without a TPU (and without ``--rehearse``) prints no result."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import loading

BENCH = loading.load_benchmark()
RUN = [sys.executable, os.path.join(loading.BENCH_DIR, "run.py")]
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def _run(*args):
    return subprocess.run(RUN + list(args), capture_output=True, text=True,
                          cwd=loading.ROOT, env=ENV, timeout=600)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", [c["name"] for c in BENCH["workloads"]])
def test_rehearsal_ends_in_a_well_formed_line(cell, trace):
    done = _run("--workload", cell, "--seed", str(2 ** 31 + 5),
                "--seconds", "1", "--trace", str(trace), "--rehearse")
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert "busy_s" not in line["device"]
    kind = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m for m in loading.cell_metrics(BENCH, cell, kind)}
    assert set(line["metrics"]) <= set(declared)
    for name, got in line["metrics"].items():
        assert got["unit"] == declared[name]["unit"]
        if declared[name]["source"] != "program_counter":
            assert got["value"] is None, f"{name} printed a device number"


def test_no_tpu_no_result_line():
    cell = BENCH["workloads"][0]["name"]
    done = _run("--workload", cell, "--seed", "1", "--seconds", "1",
                "--trace", "0")
    assert done.returncode != 0
    assert '"correct"' not in done.stdout
