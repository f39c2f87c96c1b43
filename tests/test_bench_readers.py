"""The benchmark's readers of the comm lookahead's engagement
(``bench/metrics/windows_per_sync.schedule.py``) and of the superstep-merge
pass (``sm_reused.schedule``, ``sm_s.schedule``), on hand-built
contexts."""
import pytest

from bench import harness

READ = harness.load_metric("windows_per_sync.schedule").read


def _ctx(kind="schedule", **counters):
    return harness.Context(kind=kind, setup_s=1.0, solves=2,
                           counters=counters)


@pytest.mark.parametrize("ctx", [
    _ctx(kind="partition", syncs=10, windows=40, discarded=10),
    _ctx(windows=40, discarded=10),                 # no syncs counted
    _ctx(syncs=0, windows=0, discarded=0),          # no window synced
    _ctx(syncs=10),                                 # a program without it
    _ctx(syncs=10, windows=40),
], ids=["partition", "no-syncs", "zero-syncs", "no-windows",
        "no-discarded"])
def test_windows_per_sync_absent(ctx):
    assert READ(ctx) is None


@pytest.mark.parametrize("syncs, windows, discarded, want", [
    (10, 40, 10, 3.0),
    (929, 29_371, 23_471, 5_900 / 929),
    (4, 4, 0, 1.0),
])
def test_windows_per_sync_reads_rows_used_per_sync(syncs, windows,
                                                   discarded, want):
    ctx = _ctx(syncs=syncs, windows=windows, discarded=discarded,
               attaches=3, h2d_bytes=512)
    assert READ(ctx) == pytest.approx(want)


SM_REUSED = harness.load_metric("sm_reused.schedule").read
SM_S = harness.load_metric("sm_s.schedule").read


@pytest.mark.parametrize("ctx", [
    _ctx(kind="partition", sm_priced=10, sm_reused=30),
    _ctx(syncs=10, windows=40, discarded=10),       # a program without it
    _ctx(sm_priced=10),
    _ctx(sm_priced=0, sm_reused=0),                 # no candidate priced
], ids=["partition", "no-counters", "no-reused", "none-priced"])
def test_sm_reused_absent(ctx):
    assert SM_REUSED(ctx) is None


@pytest.mark.parametrize("priced, reused, want", [
    (482, 2_486, 2_486 / 2_968),
    (10, 0, 0.0),
    (0, 7, 1.0),
])
def test_sm_reused_reads_share_of_candidates_reused(priced, reused, want):
    ctx = _ctx(sm_priced=priced, sm_reused=reused, syncs=16, attaches=2)
    assert SM_REUSED(ctx) == pytest.approx(want)


def test_sm_s_reads_inclusive_seconds_per_solve():
    span = {"count": 12, "seconds": 3.5, "p50_s": 0.25, "self_s": 3.5,
            "self_p50_s": 0.25}

    def ctx(kind="schedule", trace=None):
        return harness.Context(kind=kind, setup_s=1.0, solves=2, trace=trace)

    assert SM_S(ctx(trace={"spans": {"schedule.sm": span}})) == 1.75
    assert SM_S(ctx(trace=None)) is None
    assert SM_S(ctx(trace={"spans": {"schedule.initial": span}})) is None
    assert SM_S(ctx(kind="partition",
                    trace={"spans": {"schedule.sm": span}})) is None
