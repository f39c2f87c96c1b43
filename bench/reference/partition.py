"""Plain reference for replicated hypergraph partitions.

A partition is an array ``masks`` of length n: bit p of ``masks[v]`` is set
when node v has a copy on processor p.  The objective (paper §3.2) is
``sum_e mu_e * (lambda_e - 1)``, where ``lambda_e`` is the least number of
processors whose copies together cover every pin of e.  Here it is found by
brute force: the subsets of the P processors are tried in order of size,
and an edge's lambda is the size of the first subset that every pin's mask
meets.  Balance: every processor's load, the summed weight of the nodes it
holds a copy of, is at most ``(1 + eps) / P`` of the total weight.

This module imports nothing of the program under test.
"""
from __future__ import annotations

import numpy as np


def edge_lambdas(xpins: np.ndarray, pins: np.ndarray, masks: np.ndarray,
                 P: int) -> np.ndarray:
    """Minimum cover size of every edge (P + 1 where no subset covers)."""
    pin_masks = np.asarray(masks, dtype=np.int64)[pins]
    starts = xpins[:-1]
    E = len(starts)
    lam = np.full(E, P + 1, dtype=np.int64)
    if E == 0:
        return lam
    open_ = np.ones(E, dtype=bool)
    subsets = sorted(range(1, 1 << P), key=lambda s: (bin(s).count("1"), s))
    for s in subsets:
        hit = (pin_masks & s) != 0
        covered = np.logical_and.reduceat(hit, starts) & open_
        lam[covered] = bin(s).count("1")
        open_ &= ~covered
        if not open_.any():
            break
    return lam


def objective(xpins, pins, mu, masks, P: int) -> float:
    lam = edge_lambdas(xpins, pins, masks, P)
    return float((np.asarray(mu) * np.maximum(lam - 1, 0)).sum())


def loads(omega: np.ndarray, masks: np.ndarray, P: int) -> np.ndarray:
    bits = (np.asarray(masks, dtype=np.int64)[:, None] >> np.arange(P)) & 1
    return (bits * np.asarray(omega)[:, None]).sum(axis=0)


def overload(omega, masks, P: int, eps: float) -> float:
    """Largest processor load minus the capacity (<= 0 when balanced)."""
    cap = (1.0 + eps) / P * float(np.sum(omega))
    return float(loads(omega, masks, P).max() - cap)


def bad_masks(masks, P: int) -> int:
    """Nodes with no copy, or with a bit outside the P processors."""
    m = np.asarray(masks, dtype=np.int64)
    return int(((m <= 0) | (m >= (1 << P))).sum())


def multi_copies(masks) -> int:
    """Nodes with more than one copy (must be 0 for a base partition)."""
    m = np.asarray(masks, dtype=np.int64)
    return int(((m & (m - 1)) != 0).sum())
