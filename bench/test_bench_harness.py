"""Self-check of the benchmark's definition and files."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import pytest

from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SPEC = harness.load_spec()


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_names_units_and_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = []
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["why"])
        assert _line(c["source"])
        assert all(NAME.match(k) for k in c["reduced"])
        names.append(c["name"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        names.append(w["name"])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        names.append(m["name"])
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line(m["layer"])
    assert len(names) == len(set(names))
    assert all(_line(w) for w in SPEC["command"])
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_every_file_loads():
    cfgs = {c["name"]: c for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/")
        cfg = harness.load_json(harness.ROOT / c["file"])
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert hasattr(harness.load_kind(cfg["kind"]), "Cell")
        assert "tiny" in cfg, (f"{c['file']} has no \"tiny\" key: the "
                               "instance sizes the CPU tests run")
        assert set(cfg["tiny"]) <= set(cfg["instance"]) - {"generator"}
    for w in SPEC["workloads"]:
        assert w["config"] in cfgs
        traffic = harness.load_json(
            harness.BENCH / "workloads" / f"{w['traffic']}.json")
        assert traffic["name"] == w["traffic"]
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert callable(harness.load_metric(m["name"]).read)
    for path in (harness.BENCH / "workloads").glob("*.json"):
        harness.load_json(path)
    for path in (harness.BENCH / "configs").glob("*.json"):
        harness.load_json(path)
    for path in (harness.BENCH / "metrics").glob("*.py"):
        assert callable(harness.load_metric(path.stem).read)


def test_every_cell_reports_what_its_metrics_move():
    cells = [w["name"] for w in SPEC["workloads"]]
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for cell in cells:
        reported = {m["name"] for m in harness.cell_metrics(SPEC, cell,
                                                            False)}
        assert "setup_s" in reported and len(reported) >= 2
        assert harness.cell_metrics(SPEC, cell, True)
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in cells
            reported = {x["name"] for x in harness.cell_metrics(SPEC, cell,
                                                                False)}
            assert m["moves"] in reported, (m["name"], cell)
    layers = {}
    for m in SPEC["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_run_seconds_fits_a_full_check():
    rs = SPEC["run_seconds"]
    assert 1 <= rs <= 51
    cells = 24
    assert (2 + 14 * cells) * (rs + 60) + cells * 2 * 90 + 1200 <= 43200


def test_quick_fails_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    cell = SPEC["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "TPU" in proc.stderr


@pytest.mark.parametrize("device_kind", ["TPU v5 lite", "cpu"])
def test_peaks_table(device_kind):
    if device_kind == "cpu":
        with pytest.raises(KeyError):
            harness.peaks_for(device_kind)
    else:
        peaks = harness.peaks_for(device_kind)
        assert peaks["hbm_bytes_per_s"] == 819e9
