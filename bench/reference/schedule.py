"""Plain reference for replicated BSP schedules (paper §3.3).

A schedule is given as ``assign`` (for each node v a dict {processor:
superstep} of the copies of v that are computed), ``comms`` (a dict
``(v, dst) -> (src, superstep)``: v's value is sent from ``src`` to ``dst``
in that superstep's communication phase and is usable on ``dst`` from the
next superstep on) and ``S``, the number of supersteps.

Validity: every node is computed somewhere; every superstep index lies in
[0, S) and every processor in [0, P); a copy of v on p in superstep s finds
each parent of v present on p at s (computed on p in a superstep <= s, or
received on p in a superstep < s); a comm's value is present on its source
in its superstep, and no comm sends to its own source.

Cost, with node weights ``omega`` and communication weights ``mu``:
``sum_s max_p work(p, s) + sum_s [h_s > 0] * (L + g * h_s)``, where
``h_s = max_p max(sent(p, s), recv(p, s))``.

This module imports nothing of the program under test.
"""
from __future__ import annotations

import numpy as np

INF = float("inf")


def errors(n: int, src, dst, P: int, S: int, assign, comms) -> list[str]:
    """Every violated rule, as one message each."""
    out: list[str] = []
    parents: list[list[int]] = [[] for _ in range(n)]
    for u, v in zip(np.asarray(src).tolist(), np.asarray(dst).tolist()):
        parents[v].append(u)
    recv_at: dict[tuple[int, int], int] = {}
    for (v, d), (s_, s) in comms.items():
        recv_at[(v, d)] = s

    def present(v: int, p: int, s: int) -> bool:
        return (assign[v].get(p, INF) <= s
                or recv_at.get((v, p), INF) < s)

    if len(assign) != n:
        out.append(f"assign has {len(assign)} nodes, instance has {n}")
        return out
    for v in range(n):
        if not assign[v]:
            out.append(f"node {v} never computed")
        for p, s in assign[v].items():
            if not (0 <= p < P and 0 <= s < S):
                out.append(f"node {v} on p{p} s{s} out of range")
                continue
            for u in parents[v]:
                if not present(u, p, s):
                    out.append(f"parent {u} of {v} missing on p{p} at s{s}")
    for (v, d), (sp, s) in comms.items():
        if not (0 <= sp < P and 0 <= d < P and 0 <= s < S):
            out.append(f"comm ({v},{sp}->{d},s{s}) out of range")
        elif sp == d:
            out.append(f"comm ({v},{sp}->{d}) sends to its source")
        elif not present(v, sp, s):
            out.append(f"comm ({v},{sp}->{d},s{s}) value not on source")
    return out


def cost(omega, mu, P: int, g: float, L: float, S: int, assign,
         comms) -> float:
    """BSP cost of the schedule, recomputed from scratch."""
    work = np.zeros((S, P))
    sent = np.zeros((S, P))
    recv = np.zeros((S, P))
    for v, copies in enumerate(assign):
        for p, s in copies.items():
            work[s, p] += omega[v]
    for (v, d), (sp, s) in comms.items():
        sent[s, sp] += mu[v]
        recv[s, d] += mu[v]
    h = np.maximum(sent.max(axis=1), recv.max(axis=1))
    return float(work.max(axis=1).sum() + np.where(h > 0, L + g * h, 0).sum())
