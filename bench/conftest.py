"""Shared fixture of the benchmark's tests: each cell at a tiny size on the
CPU, with the device passes' size floors lowered so that they still attach."""
from __future__ import annotations

import pytest

from bench import harness


@pytest.fixture
def tiny(monkeypatch, tmp_path):
    """Each configuration's ``instance`` overridden by its ``"tiny"`` entry,
    the sizes the CPU tests run.  No chip run reads ``"tiny"``."""
    from repro.kernels import front_pass
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(front_pass, "DEVICE_MIN_NODES", 64)
    monkeypatch.setattr(front_pass, "DEVICE_MIN_WINDOW", 2)
    monkeypatch.setattr(front_pass, "DEVICE_MIN_STEPS", 2)
    load = harness.load_json

    def small(path):
        data = load(path)
        if path.parent.name == "configs":
            if "tiny" not in data:
                pytest.fail(f"{path.name} has no \"tiny\" key: the CPU "
                            "tests' instance sizes")
            data["instance"].update(data["tiny"])
        return data

    monkeypatch.setattr(harness, "load_json", small)
    return monkeypatch
