"""BENCHMARK.json against the limits of the benchmark's contract that
can be checked without a chip, and every name against its file."""

import os
import re

from benchmarks.harness import loading

BENCH = loading.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks"]
    assert len(BENCH["command"]) <= 32 and all(map(_line, BENCH["command"]))
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    size = os.path.getsize(os.path.join(loading.ROOT, "BENCHMARK.json"))
    assert size <= 64 * 1024


def test_configs():
    names = [c["name"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("benchmarks/")
        assert len(c["reduced"]) <= 16
        data = loading.load_json(os.path.join(loading.ROOT, c["file"]))
        assert data["reduced"] == c["reduced"]


def test_workloads_name_files_that_exist():
    names = [w["name"] for w in BENCH["workloads"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and _line(w["why"])
        mix = loading.load_traffic(w["traffic"], rehearse=False)
        assert os.path.exists(os.path.join(
            loading.BENCH_DIR, "drivers", mix["driver"] + ".py"))
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(names) // 4)


def test_metrics():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    every = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in every}) == len(every)
    cells = [w["name"] for w in BENCH["workloads"]]
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert _line(m["layer"]) and m["moves"] in e2e
        # the metric it moves is reported in every cell where this one is
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m.get("workloads", cells)) <= set(moved)
    for m in every:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= set(cells)
        kind = "end_to_end" if m["name"] in e2e else "per_layer"
        assert hasattr(loading.metric_reader(kind, m["name"]), "read")
    for cell in cells:
        mine = [m["name"] for m in loading.cell_metrics(
            BENCH, cell, "end_to_end")]
        assert "setup_s" in mine and len(mine) >= 2
        assert loading.cell_metrics(BENCH, cell, "per_layer")


def test_files_under_paths_are_named_from_a_names_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, dirs, names in os.walk(loading.BENCH_DIR):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in names:
            rel = os.path.relpath(os.path.join(base, name), loading.ROOT)
            assert ok.match(rel), rel
