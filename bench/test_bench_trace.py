"""The trace reduction, on a hand-built trace whose numbers are known and on
a small recorded trace (``testdata/``)."""
from __future__ import annotations

import pathlib
from types import SimpleNamespace as NS

import pytest

from bench import trace_reduce

DATA = pathlib.Path(__file__).resolve().parent / "testdata"


def _ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def _profile():
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=[
            _ev("solve", 1000, 9000),
            _ev("PjitFunction(find)", 1500, 400),
            _ev("solve", 12000, 4000),
        ]),
    ])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            _ev("jit_find(12)", 2000, 1000),
            _ev("jit_find(12)", 5000, 1000),
            _ev("jit_apply_(3)", 13000, 500),
        ]),
        NS(name="XLA Ops", events=[
            _ev("fusion.1", 2000, 300),
            _ev("%while.1 = (s32[]) while((s32[]) %tuple.3), "
                "condition=%region_1, body=%region_2", 2000, 1000),
            _ev("%find.3 = s32[2048,1]{1,0:T(8,128)} custom-call("
                "s32[2048,256]{1,0:T(8,128)} %fusion.2), "
                'custom_call_target="tpu_custom_call"', 2300, 700),
            _ev("fusion.1", 5000, 1000),
            _ev("copy", 13000, 500),
            _ev("late", 20000, 100),            # outside every solve
        ]),
    ])
    return NS(planes=[host, dev])


def test_hand_built_trace():
    r = trace_reduce.reduce_profile(_profile())
    assert r["window_s"] == pytest.approx(15000e-9)        # 1000 .. 16000
    assert r["busy_s"] == pytest.approx(2500e-9)
    assert r["devices"] == 1 and r["solves"] == 2
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(1300e-9)]
    assert r["modules"]["jit_find"] == {"count": 2,
                                       "seconds": pytest.approx(2000e-9)}
    assert [c[:2] for c in r["custom_calls"]["jit_find"]] == [[2300, 700]]
    gaps = dict(r["idle_gaps"])
    # 1000..2000 idle, its midpoint under the dispatch event; 3000..5000
    # and 6000..10000 inside the first solve; 10000..12000 between solves;
    # 12000..13000 and 13500..16000 inside the second
    assert gaps["PjitFunction(find)"] == pytest.approx(1000e-9)
    assert gaps["solve"] == pytest.approx((2000 + 4000 + 1000 + 2500) * 1e-9)
    assert gaps["between solves"] == pytest.approx(2000e-9)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_recorded_cpu_trace():
    """A trace the JAX profiler wrote on the CPU backend (three ``solve``
    annotations around a jitted call): the host side of the reduction.
    It has no TPU plane, so nothing is busy and every gap is host time."""
    r = trace_reduce.reduce_file(str(DATA / "cpu_fixture.xplane.pb"))
    assert r["devices"] == 0 and r["solves"] == 3
    assert r["busy_s"] == 0 and 0 < r["window_s"] < 1
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(r["window_s"])
    assert dict(r["idle_gaps"])["solve"] > 0.9 * r["window_s"]


def test_recorded_v5e_trace():
    """A trace the JAX profiler wrote on one TPU v5e chip
    (``testdata/record_v5e_trace.py``): three ``solve`` annotations around
    six runs of a jitted ``find`` whose ``while`` loop calls the Pallas
    ``front_dlam`` kernel.  The device plane's clock runs about a
    millisecond ahead of the host's here, so the first execution falls
    before the first annotation and is left out of the window."""
    r = trace_reduce.reduce_file(str(DATA / "v5e_find.xplane.pb"))
    assert r["devices"] == 1 and r["solves"] == 3
    assert 0 < r["busy_s"] < r["window_s"] < 0.01
    assert r["modules"]["jit_find"]["count"] == 5
    kernels = r["custom_calls"]["jit_find"]
    assert len(kernels) == 20            # 4 loop steps in each of 5 runs
    assert all(trace_reduce.KERNEL_TARGET in name for _, _, name in kernels)
    assert all(4000 < d < 8000 for _, d, _ in kernels)     # ns
    names = [name for name, _ in r["device_ops"]]
    assert "custom-call(" in names[0]                    # the kernel leads
    assert not any(trace_reduce.is_container(n) for n in names)
    assert all(len(n) <= trace_reduce.LABEL_CHARS for n in names)
    gaps = sum(v for _, v in r["idle_gaps"])
    assert gaps == pytest.approx(r["window_s"] - r["busy_s"])
