"""The benchmark's reader of the comm lookahead's engagement,
``bench/metrics/windows_per_sync.schedule.py``, on hand-built contexts."""
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
