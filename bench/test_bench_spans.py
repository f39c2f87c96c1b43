"""The span reduction (``span_reduce``): its span table on a hand-built
trace whose numbers are known, idle gaps named by a span that began long
before them, every key ``trace_reduce`` returns left as it reads, and the
metric readers that read the span table."""
from __future__ import annotations

import pathlib
import shutil
from types import SimpleNamespace as NS

import pytest

from bench import harness, span_reduce, trace_reduce
from bench.test_bench_trace import _ev, _profile

DATA = pathlib.Path(__file__).resolve().parent / "testdata"


def _span_profile():
    python = NS(name="python", events=[
        _ev("solve", 1000, 10000),
        _ev("partition.level", 1500, 8000, level=0, n=4096),
        _ev("device.pass", 2000, 5000, mode="fm"),
        _ev("device.find", 2100, 1000),
        _ev("PjitFunction(find)", 2200, 100),
        _ev("device.wait", 2400, 600),
        _ev("device.find", 4000, 2000),
        _ev("device.wait", 4500, 1400),
        _ev("partition.coarsen", 9600, 1000),
    ])
    # another thread's event overlaps a find: no child of it
    runtime = NS(name="runtime", events=[_ev("TpuExecute", 2250, 500)])
    return NS(planes=[NS(name="/host:CPU", lines=[python, runtime])])


def test_span_table():
    spans = span_reduce.reduce_profile(_span_profile())["spans"]
    assert set(spans) == {"partition.level", "partition.coarsen",
                          "device.pass", "device.find", "device.wait"}

    def row(name):
        r = spans[name]
        return (r["count"], r["seconds"], r["p50_s"], r["self_s"],
                r["self_p50_s"])

    ns = pytest.approx
    # the level's only direct child is the pass; the pass's are the finds
    assert row("partition.level") == (1, ns(8000e-9), ns(8000e-9),
                                      ns(3000e-9), ns(3000e-9))
    assert row("device.pass") == (1, ns(5000e-9), ns(5000e-9),
                                  ns(2000e-9), ns(2000e-9))
    # finds: 1000 - 100 (dispatch) - 600 (wait) and 2000 - 1400
    assert row("device.find") == (2, ns(3000e-9), ns(1500e-9), ns(900e-9),
                                  ns(450e-9))
    assert row("device.wait") == (2, ns(2000e-9), ns(1000e-9), ns(2000e-9),
                                  ns(1000e-9))
    assert row("partition.coarsen") == (1, ns(1000e-9), ns(1000e-9),
                                        ns(1000e-9), ns(1000e-9))


def test_late_gap_named_by_the_long_span():
    """A level with more than 256 short events in it before an idle gap:
    the gap is the level's, not the solve's.  ``trace_reduce`` looks only
    among the last 256 events begun, and so names it ``solve``."""
    events = [_ev("solve", 0, 100000), _ev("partition.level", 100, 99000)]
    events += [_ev("PjitFunction(find)", 200 + 100 * i, 50)
               for i in range(300)]
    host = NS(name="/host:CPU", lines=[NS(name="python", events=events)])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[_ev("fusion", 150, 39850)])])
    pd = NS(planes=[host, dev])
    gaps = dict(span_reduce.reduce_profile(pd)["idle_gaps"])
    # the level (100..99100) is idle 100..150 and 40000..99100
    assert gaps["partition.level"] == pytest.approx(59150e-9)
    assert gaps["solve"] == pytest.approx(1000e-9)
    assert sum(gaps.values()) == pytest.approx(100000e-9 - 39850e-9)
    old = dict(trace_reduce.reduce_profile(pd)["idle_gaps"])
    assert old["solve"] == pytest.approx(60150e-9)


def test_gap_split_at_span_edges():
    """One idle stretch over coarsening, the coarse solve and the start
    of a level is shared out among them, not put down to the middle one."""
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("solve", 0, 10000),
        _ev("partition.coarsen", 0, 2000),
        _ev("partition.initial", 2000, 2000),
        _ev("partition.level", 4000, 6000),
        _ev("device.find", 8000, 1000),
    ])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[_ev("fusion", 8500, 400)])])
    gaps = dict(span_reduce.reduce_profile(
        NS(planes=[host, dev]))["idle_gaps"])
    assert gaps == {"partition.coarsen": pytest.approx(2000e-9),
                    "partition.initial": pytest.approx(2000e-9),
                    "partition.level": pytest.approx(5000e-9),
                    "device.find": pytest.approx(600e-9)}


@pytest.mark.parametrize("source,via", [
    ("hand-built", "profile"), ("cpu_fixture.xplane.pb", "file"),
    ("v5e_find.xplane.pb", "file"), ("cpu_fixture.xplane.pb", "dir"),
    ("v5e_find.xplane.pb", "dir")])
def test_trace_reduce_keys_unchanged(source, via, tmp_path):
    if via == "profile":
        old = trace_reduce.reduce_profile(_profile())
        new = span_reduce.reduce_profile(_profile())
    else:
        old = trace_reduce.reduce_file(str(DATA / source))
    if via == "file":
        new = span_reduce.reduce_file(str(DATA / source))
    elif via == "dir":                   # laid out as start_trace writes
        run = tmp_path / "plugins" / "profile" / "2026_01_01_00_00_00"
        run.mkdir(parents=True)
        shutil.copy(DATA / source, run / "host.xplane.pb")
        new = span_reduce.reduce_dir(str(tmp_path))
    assert new.pop("spans") == {}        # recorded before the spans existed
    assert new == old


def test_reduce_dir_without_a_trace(tmp_path):
    with pytest.raises(FileNotFoundError):
        span_reduce.reduce_dir(str(tmp_path))


def _span(seconds, self_s):
    return {"count": 1, "seconds": seconds, "p50_s": seconds,
            "self_s": self_s, "self_p50_s": self_s}


SPANS = {"partition.initial": _span(3.0, 1.0),
         "partition.level": _span(8.0, 2.0),
         "partition.alternate": _span(0.5, 0.25),
         "partition.coarsen": _span(0.75, 0.75),
         "device.pass": _span(5.0, 1.5),
         "schedule.initial": _span(20.0, 16.0),
         "schedule.level": _span(2.0, 0.5),
         "windows.price": _span(4.0, 0.125)}


@pytest.mark.parametrize("metric,kind,want", [
    ("host_refine_s.partition", "partition", (1.0 + 2.0 + 0.25) / 2),
    ("device_pass_s.partition", "partition", 5.0 / 2),
    ("host_refine_s.schedule", "schedule", (16.0 + 0.5) / 2),
    ("window_s.schedule", "schedule", 4.0 / 2)])
def test_span_metric_readers(metric, kind, want):
    """Per solve in the window, from the reduced trace's span table; None
    where there are no spans, none of the metric's own, or another kind."""
    read = harness.load_metric(metric).read

    def ctx(kind=kind, trace=None):
        return harness.Context(kind=kind, setup_s=1.0, solves=2, trace=trace)

    assert read(ctx(trace={"spans": SPANS})) == pytest.approx(want)
    assert read(ctx(trace=None)) is None
    assert read(ctx(trace={"busy_s": 1.0, "window_s": 2.0})) is None
    assert read(ctx(trace={"spans": {}})) is None
    assert read(ctx(trace={"spans": {"device.find": _span(1.0, 1.0)}})) is None
    other = "schedule" if kind == "partition" else "partition"
    assert read(ctx(kind=other, trace={"spans": SPANS})) is None
