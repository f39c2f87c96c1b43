"""The control comes out as not correct, at a size a test run can hold."""
from __future__ import annotations

import io
import json
import time

import pytest

from bench import control, harness

CELLS = [w["name"] for w in harness.load_spec()["workloads"]]
# the number each kind's control has to fail: the guarantee it breaks
BROKEN = {"partition": "overload", "schedule": "cost_gap"}


def _config(cell: str) -> dict:
    spec = harness.load_spec()
    return harness.load_json(harness.BENCH / "configs"
                             / f"{harness.find_cell(spec, cell)['config']}.json")


@pytest.mark.parametrize("seed", [3, 2**33 + 4])
@pytest.mark.parametrize("cell", CELLS)
def test_control_run_is_not_correct(tiny, cell, seed):
    """A whole run with the control in the timed path's place."""
    config = _config(cell)
    kind = harness.load_kind(config["kind"])
    tiny.setattr(kind.Cell, "solve", kind.Cell.control)
    out, err = io.StringIO(), io.StringIO()
    assert harness.run(cell, seed, 0.2, False, time.perf_counter(),
                       require_tpu=False, out=out, err=err) == 0
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    assert res["correct"] is False, err.getvalue()
    got = res["checks"][BROKEN[config["kind"]]]
    assert got["value"] > got["limit"], res["checks"]


@pytest.mark.parametrize("seed", [3, 2**33 + 4])
@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_a_limit_where_sound_runs_pass(tiny, cell, seed):
    """The readings tool that sets the limits on the chip."""
    got = control.readings(cell, seed, sound=True)
    assert all(v <= 0 for v in got["sound"].values()), got
    assert got["control"][BROKEN[_config(cell)["kind"]]] > 0, got
