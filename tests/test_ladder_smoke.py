"""CI smoke for the experiment config ladder (VERDICT r03 task #7).

The two smallest rungs run end to end — profile -> allocate -> train —
through ``tools/run_ladder.py`` exactly as the full artifact run does
(``LADDER_r04.json``), at the tiny preset with reduced iterations.
"""

import pytest
import json
import os
import subprocess
import sys


@pytest.mark.slow
def test_two_smallest_rungs_run_end_to_end(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out_json = tmp_path / "ladder.json"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["SKYTPU_PRESET"] = "tiny"
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "run_ladder.py"),
         "--only", "even_4", "optimal_8", "--max-iters", "2",
         "--log-root", str(tmp_path / "logs"), "--json", str(out_json)],
        cwd=repo, env=env, capture_output=True, text=True, timeout=1500,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    record = json.loads(out_json.read_text())
    rungs = {r["config"]: r for r in record["rungs"]}
    assert set(rungs) == {"even_4", "optimal_8"}
    for name, r in rungs.items():
        assert r["exit"] == 0, r
        assert len(r["losses"]) == 2 and all(
            l is not None for l in r["losses"]
        ), r
    # the optimal rung must record a full allocation: 8 stages covering
    # every unit of the LAYER_NUM=10 model (1 embeddings + 3x10 encoder
    # parts + pooler + classifier = 33 units at the default granularity)
    alloc = rungs["optimal_8"]["allocation"]
    assert len(alloc) == 8, alloc
    assert sum(alloc) == 33, alloc
