"""The SYMGS deployment against its plain reference: the generator's DAG is
exactly the read-after-write dependencies of HPCG's smoother, the
program's schedule of it computes the smoother's ``x`` bit for bit, a
schedule that breaks a dependency cannot be replayed, and the V-cycle's
span readers read what they say."""
from __future__ import annotations

import re

import numpy as np
import pytest

from bench import gen, harness, symgs_replay
from bench.kinds.schedule import _plain
from bench.reference import schedule as rs
from bench.reference import symgs
from bench.test_bench_gen import _digest

GENERATOR = gen.load_generator("hpcg_symgs_dag")


def _edges(inst: dict) -> set[tuple[int, int]]:
    return set(zip(inst["src"].tolist(), inst["dst"].tolist()))


@pytest.mark.parametrize("dims", [(4, 4, 4), (8, 8, 13)])
def test_dag_is_the_sweeps_dependencies(dims):
    inst = GENERATOR.build(*dims)
    n = dims[0] * dims[1] * dims[2]
    assert inst["n"] == 2 * n
    assert _edges(inst) == symgs.dependencies(*dims)
    key = inst["src"] * inst["n"] + inst["dst"]
    assert np.all(np.diff(key) > 0)            # sorted, no duplicates
    nnz = np.asarray([len(c) for c in symgs.matrix(*dims)], dtype=float)
    np.testing.assert_array_equal(inst["omega"], np.concatenate([nnz, nnz]))
    np.testing.assert_array_equal(inst["mu"], np.ones(2 * n))


def test_implied_edges_are_needed():
    """Without the forward j -> backward i edges (j < i), each implied
    through forward i, the DAG is not the sweeps' dependencies."""
    dims = (4, 4, 4)
    n = 64
    inst = GENERATOR.build(*dims)
    implied = {(u, v) for u, v in _edges(inst) if u < n <= v and u != v - n}
    assert len(implied) == (len(inst["src"]) - n) // 3
    assert _edges(inst) - implied != symgs.dependencies(*dims)
    assert symgs.dependencies(*dims) - (_edges(inst) - implied) == implied


def test_sweep_is_symmetric_gauss_seidel():
    """The transcription against the smoother's algebra, A = L + D + U:
    (D + L) x1 = r - U x0, then (D + U) x2 = r - L x1."""
    dims = (4, 4, 4)
    cols = symgs.matrix(*dims)
    n = len(cols)
    A = np.zeros((n, n))
    for i, row in enumerate(cols):
        A[i, row] = symgs.OFF
        A[i, i] = symgs.DIAG
    lower, upper = np.tril(A, -1), np.triu(A, 1)
    rng = np.random.default_rng(4)
    r, x0 = rng.standard_normal(n), rng.standard_normal(n)
    x1 = np.linalg.solve(A - upper, r - upper @ x0)
    x2 = np.linalg.solve(A - lower, r - lower @ x1)
    np.testing.assert_allclose(symgs.sweep(*dims, r, x0), x2, rtol=1e-12)


def test_program_schedule_replays_bit_exact(tiny):
    """The timed path's schedule of a relabelled tiny instance (it coarsens
    once, so the V-cycle projects and refines) computes the sequential
    sweep's x exactly, on two seeded r and x0 (``bench/symgs_replay.py``,
    the tool that checks the chip's answers)."""
    from repro.core.schedule.multilevel import (MultilevelScheduleOptions,
                                                build_levels)
    config = harness.load_json(harness.BENCH / "configs" / "symgs_bsp8.json")
    assert [config["instance"][k] for k in ("nx", "ny", "nz")] == [8, 8, 13]
    cell = harness.load_kind("schedule").Cell(config, 1)
    levels, _ = build_levels(cell.dag, cell.P, MultilevelScheduleOptions(),
                             np.random.default_rng(0))
    assert len(levels) == 2
    out = symgs_replay.replays(config, 1, [11, 2**33 + 12])
    assert out["checks"] == {"schedule_errors": 0, "cost_gap": 0.0}
    assert out["bit_exact"] == {11: True, 2**33 + 12: True}


def test_replay_refuses_an_absent_parent():
    """A sound schedule replays; the same schedule with one copy moved to a
    processor where one of its parents is absent raises."""
    from repro.core.hypergraph import Dag
    from repro.core.schedule import BspInstance, best_replicated_schedule
    dims = (4, 4, 4)
    inst = GENERATOR.build(*dims)
    dag = Dag.from_arrays(inst["n"], inst["src"], inst["dst"],
                          omega=inst["omega"], mu=inst["mu"])
    P = 8
    plain = _plain(best_replicated_schedule(BspInstance(dag, P=P, g=4, L=20)))
    S, assign, comms = plain["S"], plain["assign"], plain["comms"]
    rng = np.random.default_rng(0)
    r, x0 = rng.standard_normal(64), rng.standard_normal(64)
    np.testing.assert_array_equal(
        symgs.replay(*dims, r, x0, P, S, assign, comms),
        symgs.sweep(*dims, r, x0))

    def moved():
        for v in np.flatnonzero(np.bincount(inst["dst"],
                                            minlength=inst["n"])):
            (p, s), = list(assign[v].items())[:1]
            for q in range(P):
                if q in assign[v]:
                    continue
                bad = list(assign)
                bad[v] = {**{k: t for k, t in assign[v].items() if k != p},
                          q: s}
                errs = rs.errors(inst["n"], inst["src"], inst["dst"], P, S,
                                 bad, {})
                if any(f"of {v} missing" in e for e in errs):
                    return v, bad
        raise AssertionError("no copy has a parent absent elsewhere")

    v, bad = moved()
    with pytest.raises(ValueError, match="absent"):
        symgs.replay(*dims, r, x0, P, S, bad, comms)


# sha256 of the configuration's instance, at the CPU tests' size and the
# chip's, relabelled by pool members 0 and 1
PINNED = {
    ("tiny", 0): "e90f7141e1b8831ec4182f9d02a1850f3d046b81eb088e2e3421ccd0682dc4f9",
    ("tiny", 1): "52ddde138f73b8e3ebcc85db42993044f6320e28c5c2e23c8e95267eef466206",
    ("full", 0): "7c33e3fa2f49639df0189ccf25b71d33a3ff844096a2d57284ac80792ea1e8f2",
    ("full", 1): "98d405482a97f7d480b9ed0f3eaa7f7896ec50baa0d4d1311ccc69a7dad4be37",
}


@pytest.mark.parametrize("size,seed", sorted(PINNED))
def test_instance_pinned(size, seed):
    spec = harness.load_json(harness.BENCH / "configs" / "symgs_bsp8.json")
    params = dict(spec["instance"])
    if size == "tiny":
        params.update(spec["tiny"])
    assert _digest(gen.instance(params, seed)) == PINNED[size, seed]


def test_reference_imports_nothing_of_the_program():
    path = harness.BENCH / "reference" / "symgs.py"
    code = "\n".join(line for line in path.read_text().splitlines()
                     if not line.lstrip().startswith("#"))
    assert not re.search(r"^\s*(from|import)\s+(repro|bench)\b", code,
                         re.MULTILINE)


def _span(seconds, self_s):
    return {"count": 2, "seconds": seconds, "p50_s": seconds / 2,
            "self_s": self_s, "self_p50_s": self_s / 2}


SPANS = {"schedule.coarsen": _span(0.5, 0.5),
         "schedule.initial": _span(6.0, 4.0),
         "schedule.level": _span(40.0, 3.0),
         "schedule.project": _span(0.25, 0.25),
         "schedule.advanced": _span(12.0, 8.0),
         "windows.price": _span(16.0, 0.125)}


@pytest.mark.parametrize("metric,want", [
    ("coarsen_s.schedule", 0.5 / 2),
    ("coarse_solve_s.schedule", 6.0 / 2),
    ("project_s.schedule", 0.25 / 2),
    ("advanced_s.schedule", 12.0 / 2)])
def test_vcycle_span_readers(metric, want):
    """Inclusive seconds per solve in the window; None without a trace,
    without spans, without the metric's own span, or off the schedule
    kind."""
    read = harness.load_metric(metric).read

    def ctx(kind="schedule", trace=None):
        return harness.Context(kind=kind, setup_s=1.0, solves=2, trace=trace)

    assert read(ctx(trace={"spans": SPANS})) == pytest.approx(want)
    assert read(ctx(trace=None)) is None
    assert read(ctx(trace={"busy_s": 1.0, "window_s": 2.0})) is None
    assert read(ctx(trace={"spans": {}})) is None
    assert read(ctx(trace={"spans": {"windows.price": _span(1.0, 1.0)}})) is None
    assert read(ctx(kind="partition", trace={"spans": SPANS})) is None
