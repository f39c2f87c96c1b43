"""The solver's own spans (``repro.spans``) and upload counters, on the CPU.

A small jax-path partition and schedule run under the JAX profiler, with
the device floors lowered so that their levels attach, and the trace is
reduced by the benchmark's span reduction: the spans nest as the layers
do, there is one ``device.wait`` per sync, and the answers are those of a
run with the profiler off.  ``h2d_bytes`` counts the bytes of exactly the
arrays a pass uploads.  The numpy paths stay jax-free.
"""
import glob
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench import span_reduce
from repro.core.frontier import (device_pass, device_windows, get_backend,
                                 set_backend)
from repro.core.partition import PartitionState
from repro.core.partition.cost import capacity
from repro.core.partition.heuristic import (greedy_initial,
                                            partition_with_replication)
from repro.core.schedule import (BspInstance, best_replicated_schedule,
                                 bspg_schedule)
from repro.core.schedule.multilevel import MultilevelScheduleOptions
from repro.datagen import large_row_net, large_sptrsv_dag
from repro.kernels import front_pass

SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")


@pytest.fixture
def floors(monkeypatch):
    monkeypatch.setattr(front_pass, "DEVICE_MIN_NODES", 64)
    monkeypatch.setattr(front_pass, "DEVICE_MIN_WINDOW", 2)
    monkeypatch.setattr(front_pass, "DEVICE_MIN_STEPS", 2)


def _partition():
    return partition_with_replication(large_row_net(600, seed=0), 4, 0.1,
                                      multilevel=True, frontier="jax")


def _schedule(coarsest_n=600):
    inst = BspInstance(large_sptrsv_dag(n=1200, seed=0), P=4, g=2, L=4)
    saved = get_backend()
    set_backend("jax")
    try:
        return best_replicated_schedule(
            inst, multilevel=True,
            ml_opts=MultilevelScheduleOptions(coarsest_n=coarsest_n))
    finally:
        set_backend(saved)


def _traced(solve, trace_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("solve"):
            out = solve()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    return out, jax.profiler.ProfileData.from_file(path)


def _covered_share(lines, top):
    """Share of the ``solve`` annotation's time that ``top`` spans cover."""
    solve = covered = 0.0
    for events in lines:
        for s, e, n in events:
            if n == "solve":
                solve += e - s
            elif n in top:
                covered += e - s
    return covered / solve


def _within(lines, inner, outer):
    """Events named ``inner`` (``(start, end)`` on their line), each with
    the names of the ``outer`` events on its line that enclose it."""
    out = []
    for events in lines:
        encl = [(s, e, n) for s, e, n in events if n in outer]
        for s, e, n in events:
            if n == inner:
                out.append({m for a, b, m in encl if a <= s and e <= b})
    return out


def _partition_syncs():
    return sum(t["syncs"] for t in front_pass.PARTITION_TOTALS.values())


def test_partition_spans_nest(floors, tmp_path):
    plain = _partition()
    s0 = _partition_syncs()
    traced, pd = _traced(_partition, tmp_path)
    syncs = _partition_syncs() - s0
    for a, b in zip(plain, traced):          # base and replicated
        np.testing.assert_array_equal(a.masks, b.masks)
        assert a.cost == b.cost
    lines = span_reduce.host_lines(pd)
    table = span_reduce.span_table(lines)
    assert syncs > 0 and table["device.wait"]["count"] == syncs
    assert table["device.find"]["count"] == syncs
    assert all(table[n]["count"] >= 1 for n in (
        "partition.coarsen", "partition.initial", "partition.level",
        "partition.alternate", "device.attach", "device.pass"))
    top = {"partition.coarsen", "partition.initial", "partition.level",
           "partition.alternate"}
    chain = [("device.wait", {"device.find"}),
             ("device.find", {"device.pass"}),
             ("device.pass", top - {"partition.coarsen"}),
             ("device.attach", top - {"partition.coarsen"})]
    for inner, outer in chain:
        assert all(_within(lines, inner, outer)), inner
    assert any("partition.level" in enc
               for enc in _within(lines, "device.pass", top))
    for name in top:
        assert all(_within(lines, name, {"solve"})), name
    assert _covered_share(lines, top) > 0.9


@pytest.mark.parametrize("coarsest_n", [600, 5000])
def test_schedule_spans_nest(floors, tmp_path, coarsest_n):
    """A V-cycle, and an instance at or below the coarsest size, whose
    flat solve is the V-cycle's only (initial) solve.  Each refined level
    projects once and runs the advanced heuristic once, both inside it."""
    plain = _schedule(coarsest_n)
    s0 = front_pass.SCHEDULE_TOTALS["syncs"]
    traced, pd = _traced(lambda: _schedule(coarsest_n), tmp_path)
    syncs = front_pass.SCHEDULE_TOTALS["syncs"] - s0
    assert plain.S == traced.S and plain.assign == traced.assign
    assert plain.comms == traced.comms
    assert plain.current_cost() == traced.current_cost()
    lines = span_reduce.host_lines(pd)
    table = span_reduce.span_table(lines)
    assert syncs > 0 and table["windows.wait"]["count"] == syncs
    assert table["windows.price"]["count"] == syncs
    top = {"schedule.coarsen", "schedule.initial", "schedule.level"}
    ran = top if coarsest_n < 1200 else {"schedule.initial"}
    assert {n for n in table if n in top} == ran
    assert all(_within(lines, "windows.wait", {"windows.price"}))
    assert all(_within(lines, "windows.price", top - {"schedule.coarsen"}))
    for name in ran:
        assert all(_within(lines, name, {"solve"})), name
    per_level = {"schedule.project", "schedule.advanced"}
    if "schedule.level" in ran:
        for name in per_level:
            assert (table[name]["count"]
                    == table["schedule.level"]["count"]), name
            assert all(_within(lines, name, {"schedule.level"})), name
    else:
        assert not per_level & set(table)
    assert _covered_share(lines, top) > 0.9


def test_partition_h2d_bytes(floors):
    hg = large_row_net(600, seed=0)
    P, eps = 4, 0.1
    masks = greedy_initial(hg, P, eps, np.random.default_rng(0))
    st = PartitionState(hg, P, masks=masks)
    dev = device_pass(st, capacity(hg, P, eps) + 1e-9, backend="jax")
    assert dev is not None
    try:
        held = (dev._pc, dev._contrib, dev._popcnt, dev._prim, dev._mu,
                dev._uncov, dev._lam, dev._masks)
        assert dev.h2d_bytes == sum(int(a.nbytes) for a in held)
        before = dev.h2d_bytes
        dev.fm_pass(np.random.default_rng(1).permutation(hg.n))
        blocks = sum(int(a.nbytes) for a in (
            dev._blk_edge, dev._blk_pair, dev._blk_node, dev._blk_pos))
        window = 4 * max(dev.Dmax, 1)
        per_find = ((hg.n + 1) * P            # feasibility mask, bool
                    + len(dev._blk_edge)       # active blocks, bool
                    + window + 8 * 4)          # edge window, 8 scalars
        per_apply = window + 3 * 4
        assert dev.syncs > 0
        assert dev.h2d_bytes - before == (blocks + dev.syncs * per_find
                                          + dev.apply_dispatches * per_apply)
        syncs, h2d = dev.syncs, dev.h2d_bytes
    finally:
        dev.detach()
    tot = front_pass.PARTITION_TOTALS[(hg.n, dev.use_pallas, dev.interpret)]
    assert tot["h2d_bytes"] >= h2d and tot["syncs"] >= syncs


def test_schedule_h2d_bytes(floors):
    inst = BspInstance(large_sptrsv_dag(n=300, seed=0), P=4, g=2, L=4)
    sched = bspg_schedule(inst, seed=0)
    win = device_windows(sched, "jax")
    assert win is not None
    t0 = front_pass.SCHEDULE_TOTALS["h2d_bytes"]
    (v, dst), = [next(iter(sorted(sched.comms)))]
    win.price_comm_moves(v, dst, np.arange(0, sched.S))
    rows = sum(int(a.nbytes) for a in (
        win._sent, win._recv, win._work, win._stop, win._rtop, win._wtop,
        win._scost))
    # the refresh, then one (Kp, 4) int32 array of window rows per batch
    per_batch = front_pass.DEVICE_COMM_BATCH * 4 * 4
    assert win.h2d_bytes == rows + per_batch
    win.price_comm_moves(v, dst, np.arange(0, sched.S))   # no refresh
    assert win.h2d_bytes == rows + 2 * per_batch
    assert front_pass.SCHEDULE_TOTALS["h2d_bytes"] - t0 == win.h2d_bytes


def test_numpy_paths_stay_jax_free():
    code = """
import sys
import repro.spans
from repro.core.partition.heuristic import partition_with_replication
from repro.core.schedule import BspInstance, best_replicated_schedule
from repro.core.schedule.multilevel import MultilevelScheduleOptions
from repro.datagen import large_row_net, large_sptrsv_dag
partition_with_replication(large_row_net(600, seed=0), 4, 0.1,
                           multilevel=True, frontier="numpy")
best_replicated_schedule(
    BspInstance(large_sptrsv_dag(n=1200, seed=0), P=4, g=2, L=4),
    multilevel=True, ml_opts=MultilevelScheduleOptions(coarsest_n=600))
assert "jax" not in sys.modules, "a numpy path imported jax"
print("jax-free")
"""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert "jax-free" in out.stdout
