"""Shared fixture of the benchmark's tests: each cell at a tiny size on the
CPU, with the device passes' size floors lowered so that they still attach."""
from __future__ import annotations

import pytest

from bench import harness

# tiny instances, per configuration
TINY = {"rownet_p8": {"nx": 8, "ny": 8, "nz": 16},
        "cholesky_bsp8": {"tiles": 10}}


@pytest.fixture
def tiny(monkeypatch, tmp_path):
    from repro.kernels import front_pass
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(front_pass, "DEVICE_MIN_NODES", 64)
    monkeypatch.setattr(front_pass, "DEVICE_MIN_WINDOW", 2)
    monkeypatch.setattr(front_pass, "DEVICE_MIN_STEPS", 2)
    load = harness.load_json

    def small(path):
        data = load(path)
        if path.parent.name == "configs":
            data["instance"].update(TINY[data["name"]])
        return data

    monkeypatch.setattr(harness, "load_json", small)
    return monkeypatch
