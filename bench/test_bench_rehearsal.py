"""A whole run of each cell on the CPU at a tiny size, without the look for
a chip: the window loop, the counters, the checks, the metric readers and
the trace reduction; and the same run with the timed path broken, which
has to come out not correct."""
from __future__ import annotations

import io
import json
import time

import numpy as np
import pytest

from bench import harness


def _run(cell: str, trace: bool, seconds: float = 0.2):
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run(cell, 2**33 + 17, seconds, trace, time.perf_counter(),
                     require_tpu=False, out=out, err=err)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1]), err.getvalue()


CELLS = [w["name"] for w in harness.load_spec()["workloads"]]


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal(tiny, cell, trace):
    res, err = _run(cell, trace)
    assert res["correct"] is True, err
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check ")
    spec = harness.load_spec()
    want = {m["name"] for m in harness.cell_metrics(spec, cell, trace)}
    if trace:
        assert res["device"]["window_s"] > 0
        assert "breakdown" in res
        # the CPU backend writes no TPU device plane, so the trace-read
        # metrics find nothing to read and are left out; spans are host
        # events, so the span-read metrics are there
        got = set(res["metrics"])
        assert got <= want and got
        spans = {m["name"] for m in harness.cell_metrics(spec, cell, True)
                 if m["source"] == "program_span"}
        assert spans <= got, err
        assert all(res["metrics"][name]["value"] > 0 for name in spans)
    else:
        assert set(res["metrics"]) == want
    for m in res["metrics"].values():
        # no compile inside the window once the warm-up built every program
        if m["unit"] == "compiles/solve":
            assert m["value"] >= 0
        else:
            assert m["value"] > 0 or m["unit"] == "%"


def _corrupt_partition(kind):
    def unchanged(answer):          # the solve hands back no assignment
        for res in answer:
            res.masks = np.zeros_like(res.masks)
        return answer

    def half(answer):               # half of the nodes left out
        for res in answer:
            res.masks = res.masks.copy()
            res.masks[::2] = 0
        return answer

    def altered(answer):            # one answer altered where produced
        rep = answer[1]
        rep.masks = rep.masks.copy()
        rep.masks[0] = 1 if rep.masks[0] != 1 else 2
        return answer

    return {"unchanged": unchanged, "half": half, "altered": altered}[kind]


def _corrupt_schedule(kind):
    def unchanged(sched):           # the solve hands back no assignment
        sched.assign = [dict() for _ in sched.assign]
        return sched

    def half(sched):                # half of the nodes left out
        sched.assign = [dict() if v % 2 == 0 else a
                        for v, a in enumerate(sched.assign)]
        return sched

    def altered(sched):             # one answer altered where produced
        v = next(v for v, a in enumerate(sched.assign) if a)
        (p, s), = list(sched.assign[v].items())[:1]
        P = sched.inst.P
        sched.assign = list(sched.assign)
        sched.assign[v] = {(p + 1) % P: s}
        return sched

    return {"unchanged": unchanged, "half": half, "altered": altered}[kind]


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(tiny, cell, fault):
    spec = harness.load_spec()
    config = harness.load_json(
        harness.BENCH / "configs" / f"{harness.find_cell(spec, cell)['config']}.json")
    kind = harness.load_kind(config["kind"])
    corrupt = (_corrupt_partition if config["kind"] == "partition"
               else _corrupt_schedule)(fault)
    solve = kind.Cell.solve
    calls = []

    def broken(self):
        answer = solve(self)
        calls.append(1)
        # the warm-up solve stays sound; the window's solves are broken
        return answer if len(calls) == 1 else corrupt(answer)

    tiny.setattr(kind.Cell, "solve", broken)
    res, err = _run(cell, False)
    assert res["correct"] is False, err
    assert res["failed"] >= 1
