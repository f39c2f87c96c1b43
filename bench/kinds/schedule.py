"""Replicated BSP scheduling through the public entry point.

One request is one call of ``best_replicated_schedule`` on the run's
instance, under the frontier backend the configuration file states.  The
answer is checked by ``bench.reference.schedule`` and, once the window has
closed, against the same entry under the host ``numpy`` backend.
"""
from __future__ import annotations

from bench import gen
from bench.reference import schedule as ref


def _plain(sched) -> dict:
    """The schedule as plain containers, read off the program's object."""
    return {"S": int(sched.S), "assign": [dict(a) for a in sched.assign],
            "comms": dict(sched.comms),
            "reported": float(sched.current_cost())}


class Cell:
    """Instance, entry call, counters and checks of one schedule cell."""

    def __init__(self, config: dict, seed: int) -> None:
        from repro.core.hypergraph import Dag
        from repro.kernels import front_pass

        self._front_pass = front_pass
        self.call = dict(config["call"])
        self.P = int(self.call["P"])
        self.g = float(self.call["g"])
        self.L = float(self.call["L"])
        self.inst = gen.instance(config["instance"], seed)
        self.dag = Dag.from_arrays(
            self.inst["n"], self.inst["src"], self.inst["dst"],
            omega=self.inst["omega"], mu=self.inst["mu"],
            name=config["name"])

    def describe(self) -> dict:
        return {"n": self.dag.n, "edges": int(len(self.inst["src"])),
                "P": self.P, "g": self.g, "L": self.L}

    def _run(self, backend: str, g: float | None = None):
        from repro.core.frontier import get_backend, set_backend
        from repro.core.schedule import BspInstance, best_replicated_schedule
        inst = BspInstance(self.dag, P=self.P,
                           g=self.g if g is None else g, L=self.L)
        saved = get_backend()
        set_backend(backend)
        try:
            return best_replicated_schedule(
                inst, multilevel=self.call["multilevel"],
                workers=self.call["workers"])
        finally:
            set_backend(saved)

    def solve(self):
        """One request: the timed path."""
        return self._run(self.call["backend"])

    def counters(self) -> dict:
        return dict(self._front_pass.SCHEDULE_TOTALS)

    def warmup_check(self, before: dict, after: dict, on_tpu: bool) -> None:
        if after["attaches"] - before["attaches"] <= 0:
            raise RuntimeError("warm-up: no device schedule window attached")

    def objective(self, answer) -> float | None:
        plain = _plain(answer)
        if ref.errors(self.inst["n"], self.inst["src"], self.inst["dst"],
                      self.P, plain["S"], plain["assign"], plain["comms"]):
            return None
        return ref.cost(self.inst["omega"], self.inst["mu"], self.P, self.g,
                        self.L, plain["S"], plain["assign"], plain["comms"])

    def check(self, answer) -> dict:
        """Readings of one answer by the plain reference (each limit 0)."""
        plain = _plain(answer)
        errs = ref.errors(self.inst["n"], self.inst["src"], self.inst["dst"],
                          self.P, plain["S"], plain["assign"], plain["comms"])
        if errs:
            gap = float("inf")
        else:
            gap = abs(plain["reported"] - ref.cost(
                self.inst["omega"], self.inst["mu"], self.P, self.g, self.L,
                plain["S"], plain["assign"], plain["comms"]))
        return {"schedule_errors": len(errs), "cost_gap": gap}

    def host_path(self):
        return self._run("numpy")

    @staticmethod
    def mismatch(answer, other) -> int:
        """Nodes computed differently, plus comms that differ."""
        a, b = _plain(answer), _plain(other)
        nodes = sum(x != y for x, y in zip(a["assign"], b["assign"]))
        nodes += abs(len(a["assign"]) - len(b["assign"]))
        keys = set(a["comms"]) | set(b["comms"])
        comms = sum(a["comms"].get(k) != b["comms"].get(k) for k in keys)
        return int(nodes + comms + (a["S"] != b["S"])
                   + (a["reported"] != b["reported"]))

    def control(self):
        """The control: the timed path priced with a cheaper communication
        cost ``control_g`` than the stated g, its reported cost then held to
        the stated cost model."""
        return self._run(self.call["backend"], g=float(self.call["control_g"]))
