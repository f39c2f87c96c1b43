"""Replicated hypergraph partitioning through the public entry point.

One request is one call of ``partition_with_replication`` on the run's
instance, with the arguments the configuration file states; it returns the
base (one copy per node) and the replicated partition.  The answer is
checked by ``bench.reference.partition`` and, once the window has closed,
against the same entry on the host ``numpy`` frontier.
"""
from __future__ import annotations

import numpy as np

from bench import gen
from bench.reference import partition as ref


class Cell:
    """Instance, entry call, counters and checks of one partition cell."""

    def __init__(self, config: dict, seed: int) -> None:
        from repro.core.hypergraph import Hypergraph
        from repro.kernels import front_pass

        self._front_pass = front_pass
        self.call = dict(config["call"])
        self.P = int(self.call["P"])
        self.eps = float(self.call["eps"])
        self.inst = gen.instance(config["instance"], seed)
        self.inst["mu"] = np.ones(len(self.inst["xpins"]) - 1)
        self.hg = Hypergraph.from_csr(
            self.inst["n"], self.inst["xpins"], self.inst["pins"],
            omega=self.inst["omega"], mu=self.inst["mu"],
            name=config["name"])

    def describe(self) -> dict:
        return {"n": self.hg.n, "edges": len(self.hg.edges),
                "pins": int(self.hg.num_pins), "P": self.P, "eps": self.eps}

    def _run(self, frontier: str, eps: float | None = None):
        from repro.core.partition.heuristic import partition_with_replication
        return partition_with_replication(
            self.hg, self.P, self.eps if eps is None else eps,
            mode=self.call["mode"], multilevel=self.call["multilevel"],
            frontier=frontier, workers=self.call["workers"])

    def solve(self):
        """One request: the timed path."""
        return self._run(self.call["frontier"])

    def counters(self) -> dict:
        tot = self._front_pass.PARTITION_TOTALS
        out = {k: sum(v[k] for v in tot.values())
               for k in ("attaches", "syncs", "commits", "pass_scans")}
        out["pallas_compiled"] = all(up and not interp
                                     for (_, up, interp) in tot)
        return out

    def warmup_check(self, before: dict, after: dict, on_tpu: bool) -> None:
        """The device pass has to run in the warm-up, compiled on a TPU."""
        if after["syncs"] - before["syncs"] <= 0:
            raise RuntimeError("warm-up: no device-resident partition pass "
                               "synced; the device path never attached")
        if on_tpu and not after["pallas_compiled"]:
            raise RuntimeError("warm-up: a device pass ran without the "
                               "compiled Pallas kernel")

    def objective(self, answer) -> float:
        _, rep = answer
        return ref.objective(self.inst["xpins"], self.inst["pins"],
                             self.inst["mu"], rep.masks, self.P)

    def check(self, answer) -> dict:
        """Readings of one answer by the plain reference (each limit 0)."""
        base, rep = answer
        x, pins, mu, om = (self.inst["xpins"], self.inst["pins"],
                           self.inst["mu"], self.inst["omega"])
        obj_b = ref.objective(x, pins, mu, base.masks, self.P)
        obj_r = ref.objective(x, pins, mu, rep.masks, self.P)
        return {
            "bad_masks": ref.bad_masks(base.masks, self.P)
            + ref.bad_masks(rep.masks, self.P),
            "base_multi_copies": ref.multi_copies(base.masks),
            "overload": max(ref.overload(om, base.masks, self.P, self.eps),
                            ref.overload(om, rep.masks, self.P, self.eps)),
            "cost_gap": abs(float(base.cost) - obj_b)
            + abs(float(rep.cost) - obj_r),
            "rep_minus_base": obj_r - obj_b,
        }

    def host_path(self):
        """The same entry on the host numpy frontier (outside the window)."""
        return self._run("numpy")

    @staticmethod
    def mismatch(answer, other) -> int:
        """Nodes whose base or replicated masks differ, plus cost changes."""
        (b1, r1), (b2, r2) = answer, other
        return (int(np.sum(np.asarray(b1.masks) != np.asarray(b2.masks)))
                + int(np.sum(np.asarray(r1.masks) != np.asarray(r2.masks)))
                + int(b1.cost != b2.cost) + int(r1.cost != r2.cost))

    def control(self):
        """The control: the timed path run with the balance loosened to
        ``control_eps``, its answer then held to the stated eps."""
        return self._run(self.call["frontier"],
                         eps=float(self.call["control_eps"]))
