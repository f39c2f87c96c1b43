"""The benchmark's run: set-up, measured window, checks and the result line.

Everything that belongs to one cell is found by name, so that a cell, a
configuration, a traffic mix or a metric is added as files and never as an
edit here:

* the cell ``BENCHMARK.json`` ``workloads[name]`` names its configuration
  and traffic;
* ``bench/configs/<config>.json`` holds the deployment; its ``kind`` names
  ``bench/kinds/<kind>.py``, the module that builds the instance, calls the
  program's entry point and checks the answer with ``bench/reference``;
* its ``instance.generator`` is one of ``bench/gen.py``'s built-in
  generators or names ``bench/generators/<generator>.py``;
* its ``"tiny"`` entry holds the instance sizes the CPU tests use in place
  of ``instance``'s (``bench/conftest.py``); no chip run reads it;
* ``bench/workloads/<traffic>.json`` holds the traffic parameters;
* ``bench/metrics/<metric>.py`` holds a ``read(ctx)`` for each metric that
  ``BENCHMARK.json`` lists for the cell; ``None`` leaves the metric out.
  In a traced run ``ctx.trace`` is ``bench/span_reduce.py``'s reduction
  of the profiler's trace: device time, programs and kernels, idle gaps
  named by span, and the program's ``spans``.

A run: the traffic names a pool of ``pool`` instances, member ``i`` the
configuration's instance relabelled by the fixed seed ``i``; ``--seed``
draws the order in which the run serves them, so that every seed does the
same work in another order.  Set-up builds the pool and solves its first
``warm_up`` members in that order (the warm-up, which compiles or loads
every program the window uses).  The window then serves the pool in order,
back to back and over again, as one client in a closed loop, and closes at
the end of the first whole pass over the pool that ends at or after
``--seconds``.  After the window every answer is checked by the plain
reference and the first one is compared with the program's host path.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
import tempfile
import time
import traceback

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


@dataclasses.dataclass
class Context:
    """What the metric readers see of a run."""
    kind: str
    setup_s: float
    window_s: float = 0.0
    solves: int = 0
    solve_s: list = dataclasses.field(default_factory=list)  # per request
    objective: float | None = None           # mean over the pool
    counters: dict = dataclasses.field(default_factory=dict)
    compiles: int = 0
    trace: dict | None = None
    peaks: dict | None = None                  # bench/peaks.json's entry


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def cell_metrics(spec: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports, in BENCHMARK.json's order."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def load_metric(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_kind(kind: str):
    return importlib.import_module(f"bench.kinds.{kind}")


def peaks_for(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in bench/"
                       "peaks.json; add its published peaks there")
    return table[device_kind]


class CompileCounter:
    """Counts the compile requests that reach XLA (persistent-cache loads
    included), as JAX reports them through ``jax.monitoring``, and those
    that missed the persistent cache and so compiled."""

    def __init__(self) -> None:
        self.count = 0
        self.misses = 0
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def cache_event(self, event: str, **kw) -> None:
        if event == CACHE_REQUEST_EVENT:
            self.misses += 1
        elif event == CACHE_HIT_EVENT:
            self.misses -= 1


class GcTimer:
    """Seconds the process spent in Python's cyclic garbage collector."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self._t = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.seconds += time.perf_counter() - self._t


def pool_order(pool: int, seed: int):
    """The order in which a run serves the pool's members, drawn from its
    seed."""
    import numpy as np
    return np.random.default_rng(seed).permutation(pool)


def setup_jax():
    """Point JAX's persistent cache at the checkout, unless the environment
    names one, and let it keep every program so that only a checkout's first
    run compiles."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax


def device_info(jax, chips: int) -> dict:
    devs = jax.devices()[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(jax, chips: int) -> int | None:
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, require_tpu: bool = True,
        out=sys.stdout, err=sys.stderr) -> int:
    """One run of one cell; prints the result line last on ``out``."""
    spec = load_spec()
    cell = find_cell(spec, workload)
    config = load_json(BENCH / "configs" / f"{cell['config']}.json")
    traffic = load_json(BENCH / "workloads" / f"{cell['traffic']}.json")
    if (traffic["loop"], traffic["clients"]) != ("closed", 1):
        raise ValueError(f"traffic {cell['traffic']}: only a closed loop "
                         "with one client is supported")
    pool, warm_up = int(traffic["pool"]), int(traffic["warm_up"])
    jax = setup_jax()
    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    if require_tpu and (not on_tpu or len(devices) < cell["chips"]):
        print(f"bench: cell {workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=err)
        return 1
    device = device_info(jax, cell["chips"])
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    jax.monitoring.register_event_listener(compiles.cache_event)

    kind = load_kind(config["kind"])
    order = [int(i) for i in pool_order(pool, seed)]
    suts = {i: kind.Cell(config, i) for i in order}
    sut = suts[order[0]]
    print(f"instance: {json.dumps(sut.describe())} pool_order={order}",
          file=out, flush=True)
    before = sut.counters()
    for i in order[:warm_up]:                    # warm-up: whole solves
        suts[i].solve()
    sut.warmup_check(before, sut.counters(), on_tpu)
    setup_s = time.perf_counter() - t_start
    print(f"setup: {setup_s} s", file=out, flush=True)

    ctx = Context(kind=config["kind"], setup_s=setup_s)
    answers, failure = [], None
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    c0, k0, m0 = sut.counters(), compiles.count, compiles.misses
    if trace:
        # no Python call tracing: it would record every call of the host
        # solver and slow it several-fold; host spans are the annotations
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    gc_timer = GcTimer()
    gc.callbacks.append(gc_timer)
    compile_s, gc_s = [], []
    t0 = time.perf_counter()
    t1, ends = t0, []
    try:
        while True:
            i = order[len(answers) % pool]
            c_s, g_s = compiles.seconds, gc_timer.seconds
            with jax.profiler.TraceAnnotation("solve"):
                answers.append((i, suts[i].solve()))
            t1 = time.perf_counter()
            ends.append(t1)
            compile_s.append(compiles.seconds - c_s)
            gc_s.append(gc_timer.seconds - g_s)
            if t1 - t0 >= seconds and len(answers) % pool == 0:
                break
    except Exception:                             # a failed request
        failure = traceback.format_exc()
        t1 = time.perf_counter()
    finally:
        gc.callbacks.remove(gc_timer)
        if trace:
            jax.profiler.stop_trace()
    ctx.window_s = t1 - t0
    ctx.solves = len(answers)
    ctx.compiles = compiles.count - k0
    c1 = sut.counters()
    ctx.counters = {k: c1[k] - c0[k] for k in c1
                    if isinstance(c1[k], int) and not isinstance(c1[k], bool)}
    device["memory_peak_bytes"] = memory_peak(jax, cell["chips"])
    ctx.solve_s = [b - a for a, b in zip([t0] + ends[:-1], ends)]
    print(f"window: solves={ctx.solves} window_s={ctx.window_s} "
          f"solve_s={ctx.solve_s} "
          f"compile_s={compile_s} gc_s={gc_s} "
          f"past_seconds_s={ctx.window_s - seconds} "
          f"compiles={ctx.compiles} "
          f"cache_misses={compiles.misses - m0} "
          f"counters={json.dumps(ctx.counters)}",
          file=out, flush=True)

    breakdown = None
    if trace:
        from bench import span_reduce
        t_reduce = time.perf_counter()
        try:
            ctx.trace = span_reduce.reduce_dir(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        print(f"trace: reduce_s={time.perf_counter() - t_reduce} "
              f"spans={json.dumps(ctx.trace['spans'])}", file=out, flush=True)
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
        breakdown = {"device_ops": ctx.trace["device_ops"][:10],
                     "idle_gaps": ctx.trace["idle_gaps"][:10]}
        ctx.peaks = peaks_for(device["kind"]) if on_tpu else None

    # ---- checks, after the window and the memory reading
    checks: dict[str, list] = {}

    def note(name: str, value, limit) -> None:
        value = float(value)
        if not math.isfinite(value):     # a reading that could not be made
            value = sys.float_info.max
        prev = checks.get(name)
        if prev is None or value > prev[0]:
            checks[name] = [value, limit]

    bad = [False] * len(answers)
    for k, (i, ans) in enumerate(answers):
        for name, value in suts[i].check(ans).items():
            note(name, value, 0)
            bad[k] |= value > 0
    if answers:
        first = dict(reversed(answers))          # member -> its first answer
        objs = [suts[i].objective(a) for i, a in first.items()]
        if len(first) == pool and None not in objs:
            ctx.objective = sum(objs) / pool
        i, ans = answers[0]
        mismatch = suts[i].mismatch(ans, suts[i].host_path())
        note("host_path_mismatch", mismatch, 0)
        bad[0] |= mismatch > 0
    failed = sum(bad)
    if failure is not None:
        failed += 1
        print(failure, file=err)
    correct = (failure is None and bool(answers)
               and all(v <= lim for v, lim in checks.values()))

    metrics = {}
    for m in cell_metrics(spec, workload, trace):
        value = load_metric(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    for name, (value, limit) in checks.items():
        print(f"check {name}: {value} limit {limit}", file=err)
    err.flush()
    result = {"correct": bool(correct),
              "attempted": len(answers) + int(failure is not None),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    print(json.dumps(result), file=out, flush=True)
    return 0
