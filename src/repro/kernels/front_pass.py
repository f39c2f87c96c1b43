"""Device-resident refinement passes: whole FM / replication sweeps on JAX.

The numpy frontier path prices one candidate front at a time on the host.
Shipping each front to the device would make every priced node pay a
host->device round trip.  This module instead keeps the engine's state
resident on the device across an entire refinement pass and fuses the whole
per-visit pipeline -- row gather, popcount-ordered masked-min lambda
pricing, integer cost reduction, winner argmin -- into one jitted program
that *scans* the visit permutation and stops at the first committed event.
The host then reads back exactly one (position, kind, processor) triple per
committed move (plus one terminal read per pass scan), applies the move to
both the host engine and the device mirror, and re-enters the scan at the
next position.

Correctness contract (property-tested in interpret mode):

  * **Bit-identical decisions.**  The device program is all-integer: when
    ``mu`` is integer-valued (every shipped instance), cost deltas are
    exact int32, the host's float64 thresholds collapse to integer ones
    (``delta < -1e-12``  <=>  ``delta <= -1``;  drop ``delta <= 1e-12``
    <=>  ``delta <= 0``), and ``argmin`` picks the first minimum on both
    sides -- so the committed trajectory equals the numpy frontier path's,
    move for move.  Non-integer weights fall back to the per-front path.
  * **Feasibility stays on the host.**  Capacity tests compare float64
    loads exactly as ``PartitionState.fits`` does; the host uploads the
    (n, P) feasibility mask (recomputing only columns whose load changed),
    so no device float compare can flip a knife-edge decision.
  * **One host sync per committed move.**  Each ``find`` call performs one
    blocking device->host read; a pass with M commits issues at most M + 1
    finds (the extra one proves the scan is dry; it is skipped when the
    final commit lands on the last visit position).  The counters obey
    ``commits <= syncs <= commits + pass_scans``, assertable in tests.
  * **One device dispatch per committed move.**  The engine hook *queues*
    mutations instead of dispatching them; the next ``find`` program folds
    the newest queued mutation into its own dispatch (a no-op fold when
    the queue is empty), so the commit->find cadence costs a single
    dispatch where PR 6 paid two.  Only host-side phases that mutate
    without a following find (the replication edge-guided phase) fall back
    to standalone apply programs, counted in ``apply_dispatches`` -- zero
    across any pure FM / node-sweep pass.

Layout: candidate fronts are the flat (pair, edge) expansion -- for each
visited node, P candidate masks x its incident edges -- packed into fixed
power-of-two blocks (``R_BLK`` rows, ``R_BLK // P`` node slots, a node
never split) that a ``lax.while_loop`` walks in visit order.  Blocks whose
nodes are neither boundary-at-pass-start nor dirtied by a committed move
are skipped on-device (``lax.cond``), which restores the output-sensitivity
the numpy ``GainCache`` gets from adjacency invalidation.  The per-row
lambda + cost-difference reduction optionally runs as the Pallas kernel
``gain.front_dlam`` (TPU; interpret mode on CPU) under the same
``ops._use_pallas`` switch as every other kernel in this package.

The schedule side gets the same treatment at window granularity:
``DeviceScheduleWindows`` keeps the per-superstep load rows, top-2 triples
and step costs as persistent padded device arrays and fuses the
``price_comm_moves`` / ``price_comp_moves`` gathers and the node-move
(P x P) delta-matrix fold into single jitted programs (int32, same integer
contract; float-weight instances fall back to the numpy fronts).

Program cache: every jitted program here (find, apply, window, node fold)
comes from a process-wide bounded ``lru_cache`` keyed by the static values
its body closes over, so each signature is traced, lowered and compiled
once per process and every later attach or pricer with that signature
reuses the same ``jax.jit`` object.  JAX keys its own in-memory caches by
the function object, so a fresh closure per attach would miss them all.
``program_cache_stats`` reports hits and misses.
"""
from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.partition.engine import _tables
from ..spans import span
from .gain import _NO_COVER, front_dlam

# Below this node count the per-front numpy path wins (device dispatch and
# block padding dominate); tests monkeypatch it to exercise the device path
# on small instances.
DEVICE_MIN_NODES = 4096

# Minimum schedule-window length for the fused device pricers (mirrors
# list_sched._COMM_FRONT_MIN_WINDOW's role for the numpy fronts).
DEVICE_MIN_WINDOW = 16

# Minimum touched-superstep count for the fused node-move fold.
DEVICE_MIN_STEPS = 8

# Comm windows the rebalancing sweep prices per device sync: the visited
# window and the next long ones of the pass, a batch that lasts until the
# schedule next changes (``DeviceScheduleWindows.comm_window``).
DEVICE_COMM_BATCH = 32

_R_BLK_MIN = 2048
_INT32_BUDGET = 2 ** 30  # headroom below int32 max for any partial sum

# Bound of each program cache.  A multilevel run attaches at a few levels
# (one find/apply signature each) and prices a few pow2 window lengths per
# (L, g); 64 covers that working set while a long run or a test session
# that visits many shapes cannot pin every executable for the life of the
# process (the reason ``gain._PALLAS_CACHE_SIZE`` gives).
_PROGRAM_CACHE_SIZE = 64

# Process-wide totals of the device passes, so a caller that only sees the
# public entry points can tell which levels ran on the device and how.
# Partition passes fold their counters in on ``detach``, keyed by
# (n, use_pallas, interpret): {"attaches", "syncs", "commits", "pass_scans",
# "h2d_bytes"}.
PARTITION_TOTALS: dict[tuple[int, bool, bool], dict[str, int]] = {}
# Every ``DeviceScheduleWindows`` built, every host sync it made, every
# byte it uploaded, every comm window it priced and every priced-ahead
# window a mutation dropped unused.
SCHEDULE_TOTALS = {"attaches": 0, "syncs": 0, "h2d_bytes": 0, "windows": 0,
                   "discarded": 0}


def _integer_valued(a: np.ndarray) -> bool:
    a = np.asarray(a, dtype=np.float64)
    return bool(np.all(np.isfinite(a)) and np.all(a == np.rint(a)))


def _pow2(x: int) -> int:
    return 1 << max(int(x) - 1, 1).bit_length()


def _popcount_columns(P: int) -> np.ndarray:
    """(2^P,) int32 popcounts in popcount-column order, ``_NO_COVER`` at
    column 0 (the empty subset)."""
    order_pc = _tables(P)[2]
    return np.concatenate(([_NO_COVER], order_pc)).astype(np.int32)


# ==========================================================================
# Partition side
# ==========================================================================

def attach(state, cap: float):
    """Build a ``DevicePartitionPass`` mirroring ``state``, or None.

    Returns None -- caller falls back to the per-front path -- when the
    instance is too small to pay for device dispatch (``DEVICE_MIN_NODES``),
    mu is not integer-valued (the all-integer device program would not be
    bit-identical), or an int32 partial sum could overflow.  On success the
    engine's ``device`` hook is set so every ``apply``/``undo`` keeps the
    device mirror in lockstep.
    """
    if state.backend != "numpy" or state.device is not None:
        return None
    hg = state.hg
    if hg.n < DEVICE_MIN_NODES:
        return None
    if not _integer_valued(state.mu) or np.any(state.mu < 0):
        return None
    if np.any(state.masks == 0):
        # host derives a -1 primary for unassigned nodes, the device table
        # cannot; refinement never unassigns, so the check holds for a pass
        return None
    mu_i = np.rint(state.mu).astype(np.int64)
    # worst-case |delta| for one candidate: sum of incident mu * (P - 1)
    deg = np.diff(state.xinc)
    if len(state.inc_edges):
        wsum = np.bincount(
            np.repeat(np.arange(hg.n), deg), weights=mu_i[state.inc_edges],
            minlength=hg.n)
    else:
        wsum = np.zeros(hg.n)
    if wsum.max(initial=0.0) * max(state.P - 1, 1) >= _INT32_BUDGET:
        return None
    with span("device.attach", n=hg.n):
        dev = DevicePartitionPass(state, cap)
    state.device = dev
    return dev


@functools.lru_cache(maxsize=_PROGRAM_CACHE_SIZE)
def find_program(mode: str, *, n: int, E: int, P: int, R_blk: int,
                 use_pallas: bool, interpret: bool):
    """The jitted find program of a ``"fm"`` or ``"rep"`` pass.

    ``n``/``E`` are the real node and edge counts (row n / E of the device
    tables is the dummy), ``R_blk`` the pow2 rows of one front block.  The
    popcount columns are a constant of ``P``.  Everything else is an
    argument, so the program can be lowered from shapes alone.  Cached per
    signature: equal arguments return the same ``jax.jit`` object.
    """
    pc = jnp.asarray(_popcount_columns(P))
    nsub = 1 << P
    B_blk = R_blk // P
    BIG = np.int32(np.iinfo(np.int32).max)
    qbits = jnp.asarray((np.int64(1) << np.arange(P)).astype(np.int32))
    allq = jnp.arange(P, dtype=jnp.int32)
    Mp = -(-nsub // 128) * 128
    is_rep = mode == "rep"

    def dlam_of(rows, lam_old):
        if use_pallas:
            pc_p = pc
            if Mp != nsub:
                rows = jnp.pad(rows, ((0, 0), (0, Mp - nsub)),
                               constant_values=1)
                pc_p = jnp.pad(pc, (0, Mp - nsub), constant_values=_NO_COVER)
            return front_dlam(rows, pc_p, lam_old, interpret=interpret)
        lam_new = jnp.min(
            jnp.where(rows == 0, pc[None, :], _NO_COVER),
            axis=1).astype(jnp.int32)
        return jnp.maximum(lam_new - 1, 0) - jnp.maximum(lam_old - 1, 0)

    def find(uncov, lam, masks, mu, contrib, fits, prim, popcnt,
             blk_edge, blk_pair, blk_node, blk_pos, active,
             nb, b0, start_pos, resume_p, maxrep,
             av, aold, anew, ae_win):
        # fused apply: fold the last queued host mutation into this
        # program (av = n with aold == anew encodes "nothing pending" --
        # diff is all zeros, ae_win all-dummy, masks[n] is the dummy
        # row), then run the scan on the updated buffers
        adiff = contrib[anew] - contrib[aold]
        avalid = ae_win < E
        uncov = uncov.at[ae_win].add(
            jnp.where(avalid[:, None], adiff[None, :], 0))
        arows = uncov[ae_win]
        alam = jnp.min(
            jnp.where(arows == 0, pc[None, :], _NO_COVER),
            axis=1).astype(jnp.int32)
        lam = lam.at[ae_win].set(jnp.where(avalid, alam, lam[ae_win]))
        masks = masks.at[av].set(anew)

        def eval_block(b):
            edges = blk_edge[b]
            pairs = blk_pair[b]
            nodes = blk_node[b]
            poss = blk_pos[b]
            m_old = masks[nodes]
            qof = pairs % P
            slot = pairs // P
            m_row = m_old[slot]
            rows0 = uncov[edges]
            lam_old = lam[edges]
            mu_row = mu[edges]
            in_win = (poss >= start_pos) & (poss < n)

            def deltas_for(cand_row):
                rows = (rows0 + contrib[cand_row] - contrib[m_row])
                terms = dlam_of(rows, lam_old) * mu_row
                return jax.ops.segment_sum(
                    terms, pairs,
                    num_segments=B_blk * P).reshape(B_blk, P)

            if not is_rep:
                # FM: candidate masks 1 << q, primary excluded
                d_move = deltas_for(qbits[qof])
                feas = fits[nodes] & (allq[None, :]
                                      != prim[m_old][:, None])
                masked = jnp.where(feas, d_move, BIG)
                bestq = jnp.argmin(masked, axis=1).astype(jnp.int32)
                bestd = jnp.take_along_axis(
                    masked, bestq[:, None], axis=1)[:, 0]
                elig = (bestd <= -1) & in_win
                sel = jnp.argmax(elig)
                found = elig[sel]
                return (jnp.where(found, poss[sel], n),
                        jnp.int32(0),
                        jnp.where(found, bestq[sel], 0))

            # replication: add step then drop step, host visit order
            k = popcnt[m_old]
            unset = ((m_old[:, None] >> allq[None, :]) & 1) == 0
            d_add = deltas_for(m_row | qbits[qof])
            feas_add = fits[nodes] & unset & (k < maxrep)[:, None]
            masked = jnp.where(feas_add, d_add, BIG)
            bestq = jnp.argmin(masked, axis=1).astype(jnp.int32)
            bestd = jnp.take_along_axis(
                masked, bestq[:, None], axis=1)[:, 0]
            resuming = resume_p >= 0
            add_sup = resuming & (poss == start_pos)
            has_add = (bestd <= -1) & in_win & ~add_sup
            d_drop = deltas_for(m_row & ~qbits[qof])
            minp = jnp.where(add_sup, resume_p, 0)
            elig_drop = (~unset & (k > 1)[:, None] & (d_drop <= 0)
                         & (allq[None, :] >= minp[:, None])
                         & in_win[:, None])
            dropp = jnp.argmax(elig_drop, axis=1).astype(jnp.int32)
            has_drop = jnp.take_along_axis(
                elig_drop, dropp[:, None], axis=1)[:, 0]
            event = has_add | has_drop
            sel = jnp.argmax(event)
            found = event[sel]
            kind = jnp.where(has_add[sel], 0, 1).astype(jnp.int32)
            q = jnp.where(has_add[sel], bestq[sel], dropp[sel])
            return (jnp.where(found, poss[sel], n), kind,
                    jnp.where(found, q, 0))

        def cond(c):
            b, pos, _, _ = c
            return (b < nb) & (pos >= n)

        def body(c):
            b = c[0]
            pos, kind, q = jax.lax.cond(
                active[b], eval_block,
                lambda _b: (jnp.int32(n), jnp.int32(0), jnp.int32(0)), b)
            return b + 1, pos, kind, q

        _, pos, kind, q = jax.lax.while_loop(
            cond, body,
            (b0, jnp.int32(n), jnp.int32(0), jnp.int32(0)))
        # donated buffers ride back out; the stacked triple keeps the
        # host read down to a single transfer
        return uncov, lam, masks, jnp.stack([pos, kind, q])

    return functools.partial(jax.jit, donate_argnums=(0, 1, 2))(find)


@functools.lru_cache(maxsize=_PROGRAM_CACHE_SIZE)
def _apply_program(E: int):
    """The standalone apply program for ``E`` real edges (row E is the
    dummy); ``contrib`` and ``pc`` are arguments."""

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def apply_(uncov, lam, masks, v, old, new, e_win, contrib, pc):
        diff = contrib[new] - contrib[old]
        valid = e_win < E
        uncov = uncov.at[e_win].add(
            jnp.where(valid[:, None], diff[None, :], 0))
        rows = uncov[e_win]
        lam_new = jnp.min(
            jnp.where(rows == 0, pc[None, :], _NO_COVER),
            axis=1).astype(jnp.int32)
        lam = lam.at[e_win].set(jnp.where(valid, lam_new, lam[e_win]))
        masks = masks.at[v].set(new)
        return uncov, lam, masks

    return apply_


class DevicePartitionPass:
    """Device mirror of a ``PartitionState`` plus the fused pass programs.

    Columns of ``uncov``/``contrib`` are stored pre-permuted in popcount
    order (column 0 = subset 0), so lambda pricing is a pure masked min
    with no per-call gather.  A dummy edge row E (mu 0, all-zero uncov) and
    a dummy node row n (infeasible everywhere) absorb all padding.
    """

    def __init__(self, state, cap: float) -> None:
        self.state = state
        self.cap = float(cap)
        self.h2d_bytes = 0   # host -> device bytes uploaded (``_put``)
        # on a TPU the Pallas kernel always runs compiled; interpret mode
        # exists only for the CPU backend, where ``ops.force("pallas")``
        # selects it in tests
        self.interpret = jax.default_backend() != "tpu"
        from .ops import _use_pallas
        self.use_pallas = _use_pallas()
        hg = state.hg
        self.n = hg.n
        self.P = state.P
        self.nsub = 1 << state.P
        self.E = len(hg.edges)
        self.xinc = np.asarray(state.xinc, dtype=np.int64)
        self.inc_edges_np = np.asarray(state.inc_edges, dtype=np.int64)
        self.deg = np.diff(self.xinc).astype(np.int64)
        self.Dmax = int(self.deg.max(initial=0))
        max_rows = self.P * max(self.Dmax, 1)
        self.R_blk = max(_R_BLK_MIN, _pow2(max_rows))
        self.B_blk = self.R_blk // self.P
        # column permutation: subset 0 first, then popcount order
        self.colmap = np.concatenate(
            ([0], np.asarray(state._order, dtype=np.int64)))
        self._pc = self._put(_popcount_columns(self.P))
        self._contrib = self._put(
            np.ascontiguousarray(state._contrib[:, self.colmap],
                                 dtype=np.int32))
        self._popcnt = self._put(np.asarray(state.popcnt, dtype=np.int32))
        prim = np.maximum(
            np.array([int(m).bit_length() - 1 for m in range(self.nsub)],
                     dtype=np.int32), 0)
        self._prim = self._put(prim)
        mu_i = np.zeros(self.E + 1, dtype=np.int32)
        mu_i[:self.E] = np.rint(state.mu).astype(np.int32)
        self._mu = self._put(mu_i)
        self._owner = np.repeat(np.arange(self.n), self.deg)  # bnd scatter
        # mutation queue: host applies are *deferred* and fused into the
        # next find program, so a committed move costs one dispatch, not two
        self._pending: list[tuple[int, int, int]] = []
        self._refresh_from_host()
        self._fits = np.zeros((self.n + 1, self.P), dtype=bool)
        self._last_loads = None
        self._dirty = np.zeros(self.n, dtype=bool)
        self._apply_fn = _apply_program(self.E)
        kw = dict(n=self.n, E=self.E, P=self.P, R_blk=self.R_blk,
                  use_pallas=self.use_pallas, interpret=self.interpret)
        self._find_fm = find_program("fm", **kw)
        self._find_rep = find_program("rep", **kw)
        # instrumentation (sync = blocking device->host read)
        self.syncs = 0
        self.commits = 0
        self.pass_scans = 0
        self.apply_dispatches = 0  # standalone apply programs dispatched

    # ------------------------------------------------------------ buffers
    def _put(self, a: np.ndarray) -> jax.Array:
        """Upload one host array, counted in ``h2d_bytes``."""
        self.h2d_bytes += a.nbytes
        return jnp.asarray(a)

    def _scalars(self, *xs: int) -> list:
        """Upload int32 scalars, counted in ``h2d_bytes``."""
        self.h2d_bytes += 4 * len(xs)
        return [jnp.int32(x) for x in xs]

    def _refresh_from_host(self) -> None:
        """Full host -> device upload of uncov / lambdas / masks."""
        st = self.state
        self._pending.clear()   # host state already includes queued moves
        uncov_p = np.zeros((self.E + 1, self.nsub), dtype=np.int32)
        uncov_p[:self.E] = st.uncov[:, self.colmap]
        self._uncov = self._put(uncov_p)
        # device lambda: masked-min value; differs from the engine's only
        # on rows with no assigned pins (engine 0, masked-min 1) -- the
        # relu(cost) terms agree, so deltas are unaffected
        lam = np.ones(self.E + 1, dtype=np.int32)
        lam[:self.E] = np.where(st.uncov[:, 0] == 0, 1, st.edge_lambda)
        self._lam = self._put(lam)
        masks = np.ones(self.n + 1, dtype=np.int32)
        masks[:self.n] = st.masks
        self._masks = self._put(masks)

    def detach(self) -> None:
        self.state.device = None
        tot = PARTITION_TOTALS.setdefault(
            (self.n, self.use_pallas, self.interpret),
            {"attaches": 0, "syncs": 0, "commits": 0, "pass_scans": 0,
             "h2d_bytes": 0})
        tot["attaches"] += 1
        tot["syncs"] += self.syncs
        tot["commits"] += self.commits
        tot["pass_scans"] += self.pass_scans
        tot["h2d_bytes"] += self.h2d_bytes

    # -------------------------------------------------------- engine hook
    def apply(self, v: int, old: int, new: int) -> None:
        """Mirror one host ``apply``/``undo`` mutation.

        Deferred: the mutation is queued and fused into the *next* find
        program (``_call_find``), so the common commit->find cadence costs
        one device dispatch per move instead of two.  ``flush`` forces the
        queue down when device buffers must be current with no find in
        sight (tests, detach-and-inspect).
        """
        self._pending.append((int(v), int(old), int(new)))

    def _edge_window(self, v: int) -> np.ndarray:
        """v's incident edges padded to Dmax with the dummy edge E."""
        w = np.full(self.Dmax if self.Dmax else 1, self.E, dtype=np.int32)
        if v < self.n:
            d = int(self.deg[v])
            if d:
                w[:d] = self.inc_edges_np[self.xinc[v]:self.xinc[v] + d]
        return w

    def _dispatch_apply(self, v: int, old: int, new: int) -> None:
        self._uncov, self._lam, self._masks = self._apply_fn(
            self._uncov, self._lam, self._masks,
            *self._scalars(v, old, new), self._put(self._edge_window(v)),
            self._contrib, self._pc)
        self.apply_dispatches += 1

    def flush(self) -> None:
        """Dispatch every queued mutation as standalone apply programs."""
        pending, self._pending = self._pending, []
        for v, old, new in pending:
            self._dispatch_apply(v, old, new)

    # ------------------------------------------------------- block builder
    def _build_blocks(self, perm: np.ndarray) -> None:
        """Pack the pass's flat (pair, edge) expansion into device blocks."""
        P, R_blk, B_blk = self.P, self.R_blk, self.B_blk
        n = len(perm)
        deg = self.deg[perm]
        d = np.maximum(deg, 1)
        rpn = P * d
        cum = np.cumsum(rpn)
        bounds = [0]
        while bounds[-1] < n:
            i = bounds[-1]
            base = int(cum[i - 1]) if i else 0
            j = int(np.searchsorted(cum, base + R_blk, side="right"))
            bounds.append(min(max(j, i + 1), i + B_blk, n))
        NB = len(bounds) - 1
        NBp = _pow2(NB)
        bounds = np.asarray(bounds, dtype=np.int64)
        total = int(cum[-1])
        owner = np.repeat(np.arange(n, dtype=np.int64), rpn)
        starts = cum - rpn
        off = np.arange(total, dtype=np.int64) - starts[owner]
        q = off // d[owner]
        eoff = off % d[owner]
        vo = perm[owner]
        has = deg[owner] > 0
        if len(self.inc_edges_np):
            src = np.minimum(self.xinc[vo] + eoff,
                             len(self.inc_edges_np) - 1)
            edges = np.where(has, self.inc_edges_np[src], self.E)
        else:
            edges = np.full(total, self.E, dtype=np.int64)
        blk_of = np.searchsorted(bounds, owner, side="right") - 1
        pair = (owner - bounds[blk_of]) * P + q
        rows_at = np.concatenate(([0], cum))[bounds]
        blk_edge = np.full((NBp, R_blk), self.E, dtype=np.int32)
        # padding rows funnel into the last (slot, q) segment; their edge is
        # the dummy E (mu 0), so they add exact zeros wherever they land
        blk_pair = np.full((NBp, R_blk), B_blk * P - 1, dtype=np.int32)
        blk_node = np.full((NBp, B_blk), self.n, dtype=np.int32)
        blk_pos = np.full((NBp, B_blk), self.n, dtype=np.int32)
        for b in range(NB):
            r0, r1 = int(rows_at[b]), int(rows_at[b + 1])
            blk_edge[b, :r1 - r0] = edges[r0:r1]
            blk_pair[b, :r1 - r0] = pair[r0:r1]
            i0, i1 = int(bounds[b]), int(bounds[b + 1])
            blk_node[b, :i1 - i0] = perm[i0:i1]
            blk_pos[b, :i1 - i0] = np.arange(i0, i1)
        self._bounds = bounds
        self._nb = NB
        self._blk_edge = self._put(blk_edge)
        self._blk_pair = self._put(blk_pair)
        self._blk_node = self._put(blk_node)
        self._blk_pos = self._put(blk_pos)

    # --------------------------------------------------------- host helpers
    def _boundary_start(self, rep: bool) -> np.ndarray:
        """Nodes that can hold an event at pass start (visit-time exact
        elsewhere: any other node must be dirtied first -- see module
        docstring)."""
        st = self.state
        flag = np.asarray(st.edge_lambda > 1)
        if len(self._owner):
            cnt = np.bincount(self._owner[flag[self.inc_edges_np]],
                              minlength=self.n)
            bnd = cnt > 0
        else:
            bnd = np.zeros(self.n, dtype=bool)
        if rep:
            bnd = bnd | (np.asarray(st.popcnt[st.masks]) > 1)
        return bnd

    def _fits_now(self):
        """(n+1, P) feasibility, recomputing only load-shifted columns."""
        st = self.state
        loads = np.asarray(st.loads, dtype=np.float64)
        if self._last_loads is None:
            changed = np.ones(self.P, dtype=bool)
        else:
            changed = loads != self._last_loads
        for p in np.flatnonzero(changed):
            self._fits[:self.n, p] = st.omega + loads[p] <= self.cap
        self._last_loads = loads.copy()
        return self._put(self._fits)

    def _active_blocks(self, bnd_start: np.ndarray):
        av = (bnd_start | self._dirty)[self._perm]
        counts = np.add.reduceat(av.astype(np.int64), self._bounds[:-1])
        active = np.zeros(len(self._blk_edge), dtype=bool)
        active[:self._nb] = counts[:self._nb] > 0
        return self._put(active)

    def _mark_dirty(self, v: int) -> None:
        hg = self.state.hg
        self._dirty[hg.adj_nodes[hg.xadj[v]:hg.xadj[v + 1]]] = True
        self._dirty[v] = True

    def _call_find(self, fn, b0: int, start_pos: int, resume_p: int,
                   maxrep: int, bnd_start: np.ndarray):
        with span("device.find"):
            # fold the newest queued mutation into this find (one dispatch
            # per committed move); older queue entries -- only possible
            # after host-side phases between passes -- still go out as
            # standalone applies
            if self._pending:
                *older, (av, aold, anew) = self._pending
                self._pending = []
                for ov, oold, onew in older:
                    self._dispatch_apply(ov, oold, onew)
            else:
                av, aold, anew = self.n, 1, 1  # no-op: dummy row, zero diff
            self._uncov, self._lam, self._masks, out = fn(
                self._uncov, self._lam, self._masks, self._mu,
                self._contrib, self._fits_now(), self._prim, self._popcnt,
                self._blk_edge, self._blk_pair, self._blk_node,
                self._blk_pos, self._active_blocks(bnd_start),
                *self._scalars(self._nb, b0, start_pos, resume_p, maxrep,
                               av, aold, anew),
                self._put(self._edge_window(av)))
            with span("device.wait"):
                host = np.asarray(out)          # THE host sync
            self.syncs += 1
        pos, kind, q = (int(x) for x in host)
        return pos, kind, q

    def _block_of(self, pos: int) -> int:
        return int(np.searchsorted(self._bounds, pos, side="right")) - 1

    # ------------------------------------------------------------ FM pass
    def run_fm(self, rng: np.random.Generator, passes: int) -> None:
        """Device-resident ``fm_refine`` sweep (decision-identical)."""
        st = self.state
        for _ in range(passes):
            perm = rng.permutation(self.n)
            if not self.fm_pass(perm):
                break
        return st.masks

    def fm_pass(self, perm: np.ndarray) -> bool:
        with span("device.pass", mode="fm"):
            st = self.state
            self._perm = np.asarray(perm, dtype=np.int64)
            self._dirty[:] = False
            bnd = self._boundary_start(rep=False)
            self._build_blocks(self._perm)
            pos, improved = 0, False
            while pos < self.n:
                fpos, _, q = self._call_find(
                    self._find_fm, self._block_of(pos), pos, -1, 0, bnd)
                if fpos >= self.n:
                    self.pass_scans += 1
                    break
                v = int(self._perm[fpos])
                st.apply(v, 1 << q)
                st.commit()
                self.commits += 1
                self._mark_dirty(v)
                improved = True
                pos = fpos + 1
            else:
                self.pass_scans += 1
            return improved

    # ----------------------------------------------------- replication pass
    def rep_pass(self, perm: np.ndarray, max_replicas: int | None) -> bool:
        """Device-resident add/drop node sweep of ``replicate_local_search``
        (the edge-guided phase stays on the host engine; its mutations reach
        the device through the engine hook)."""
        with span("device.pass", mode="rep"):
            st = self.state
            self._perm = np.asarray(perm, dtype=np.int64)
            self._dirty[:] = False
            bnd = self._boundary_start(rep=True)
            self._build_blocks(self._perm)
            maxrep = (self.P + 1 if max_replicas is None
                      else int(max_replicas))
            pos, resume_p, improved = 0, -1, False
            while pos < self.n:
                fpos, kind, q = self._call_find(
                    self._find_rep, self._block_of(pos), pos, resume_p,
                    maxrep, bnd)
                if fpos >= self.n:
                    self.pass_scans += 1
                    break
                v = int(self._perm[fpos])
                m = int(st.masks[v])
                if kind == 0:  # add replica q, move on (host `continue`)
                    st.apply(v, m | (1 << q))
                    pos, resume_p = fpos + 1, -1
                else:          # drop replica q, resume the node at q + 1
                    st.apply(v, m & ~(1 << q))
                    pos, resume_p = fpos, q + 1
                st.commit()
                self.commits += 1
                self._mark_dirty(v)
                improved = True
            else:
                self.pass_scans += 1
            return improved


# ==========================================================================
# Schedule side
# ==========================================================================

def schedule_device_supported(sched) -> bool:
    """Integer contract check: the fused int32 programs are bit-identical
    to the float64 numpy fronts only for integral weights/parameters."""
    inst = sched.inst
    return (_integer_valued(inst.dag.mu) and _integer_valued(inst.dag.omega)
            and float(inst.L) == int(inst.L) and float(inst.g) == int(inst.g))


class DeviceScheduleWindows:
    """Persistent device mirror of the schedule's per-superstep rows.

    Holds ``sent``/``recv``/``work`` (S, P), the top-2 triples and step
    costs as padded int32 jnp arrays, refreshed lazily after each commit
    (``mark_dirty``).  The window pricers return the same float64 deltas as
    ``schedule_front.price_comm_moves`` / ``price_comp_moves`` /
    ``price_node_moves`` -- integer device arithmetic plus the host's exact
    float64 scalar terms -- so every decision matches the numpy fronts.

    Comm windows price in batches of up to ``DEVICE_COMM_BATCH`` rows, one
    sync each; ``comm_window`` keeps the rows a batch priced ahead in
    ``ahead`` until they are visited or ``mark_dirty`` drops them, so a
    row is only ever read against the state it was priced on.
    """

    def __init__(self, sched) -> None:
        self.sched = sched
        self.P = sched.inst.P
        self.L = int(sched.inst.L)
        self.g = int(sched.inst.g)
        self._dirty = True
        self.ahead: dict[tuple[int, int], np.ndarray] = {}
        self.syncs = 0
        self.h2d_bytes = 0
        self.windows = 0     # comm windows priced
        self.discarded = 0   # priced ahead, dropped unused by a mutation
        SCHEDULE_TOTALS["attaches"] += 1

    def _tally(self, key: str, k: int) -> None:
        """Add ``k`` to counter ``key`` here and in ``SCHEDULE_TOTALS``."""
        setattr(self, key, getattr(self, key) + k)
        SCHEDULE_TOTALS[key] += k

    def _put(self, a: np.ndarray) -> jax.Array:
        """Upload one host array, counted in ``h2d_bytes``."""
        self._tally("h2d_bytes", a.nbytes)
        return jnp.asarray(a)

    def mark_dirty(self) -> None:
        """The schedule changed: refresh before the next price and drop
        every row priced ahead."""
        self._dirty = True
        self._tally("discarded", len(self.ahead))
        self.ahead.clear()

    def _refresh(self) -> None:
        s = self.sched
        self.S = s.S
        self.Sp = _pow2(self.S)
        P = self.P

        def rows(ll):
            a = np.zeros((self.Sp, P), dtype=np.int32)
            a[:self.S] = np.asarray(ll[:self.S])
            return self._put(a)

        def tops(tt):
            a = np.zeros((self.Sp, 3), dtype=np.int32)
            a[:self.S] = np.asarray(tt[:self.S])
            return self._put(a)

        self._sent, self._recv, self._work = (
            rows(s.sent), rows(s.recv), rows(s.work))
        self._stop, self._rtop, self._wtop = (
            tops(s._stop), tops(s._rtop), tops(s._wtop))
        sc = np.zeros(self.Sp, dtype=np.int32)
        sc[:self.S] = np.asarray(s._scost[:self.S])
        self._scost = self._put(sc)
        self._dirty = False

    def _price_windows(self, kind: str, wins, W: int, Kp: int) -> np.ndarray:
        """One sync: refresh if dirty, upload the window rows ``wins`` --
        ``(lo, a, b, weight)`` each, see ``_win_program`` -- as one
        zero-padded (Kp, 4) int32 array, run the ``kind`` window program
        over ``W``, the longest window, and read the whole result back."""
        if self._dirty:
            self._refresh()
        packed = np.zeros((Kp, 4), dtype=np.int32)
        packed[:len(wins)] = wins
        fn = _win_program(kind, Kp, _pow2(W), self.L, self.g)
        out = fn(self._sent if kind == "comm" else self._work, self._recv,
                 self._stop, self._rtop, self._wtop, self._scost,
                 self._put(packed))
        self._tally("syncs", 1)
        with span("windows.wait"):
            return np.asarray(out)

    def price_comm_batch(self, items) -> list[np.ndarray]:
        """Price comm windows in one sync.  ``items`` holds up to
        ``DEVICE_COMM_BATCH`` tuples ``(v, dst, lo, W)``; row i equals
        ``schedule_front.price_comm_moves(sched, v, dst, range(lo, lo + W))``
        bit for bit."""
        with span("windows.price", kind="comm"):
            sched = self.sched
            mu = sched.inst.dag.mu
            heads = [sched.comms[(v, dst)] for v, dst, _, _ in items]
            out = self._price_windows(
                "comm",
                [(lo, src, dst, int(mu[v]))
                 for (v, dst, lo, _), (src, _) in zip(items, heads)],
                max(W for *_, W in items),
                _pow2(max(len(items), DEVICE_COMM_BATCH)))
            self._tally("windows", len(items))
            rows = []
            for (v, dst, lo, W), (src, s), got in zip(items, heads, out):
                d0 = sched._comm_step_delta(s, src, dst, -mu[v])
                deltas = d0 + got[:W].astype(np.float64)
                if lo <= s < lo + W:
                    deltas[s - lo] = 0.0
                rows.append(deltas)
            return rows

    def comm_window(self, v: int, dst: int, lo: int, W: int,
                    ahead) -> np.ndarray:
        """Deltas of comm ``(v, dst)`` over supersteps ``lo .. lo + W - 1``.

        Read from ``self.ahead`` when a batch priced them since the last
        ``mark_dirty``; otherwise priced in a new batch together with the
        next ``DEVICE_COMM_BATCH - 1`` windows that the iterator ``ahead``
        yields (``(v, dst, lo, W)`` each, in visit order), whose rows wait
        in ``self.ahead``.
        """
        row = self.ahead.pop((v, dst), None)
        if row is None:
            items = [(v, dst, lo, W),
                     *itertools.islice(ahead, DEVICE_COMM_BATCH - 1)]
            row, *rest = self.price_comm_batch(items)
            self.ahead = {(it[0], it[1]): r
                          for it, r in zip(items[1:], rest)}
        return row

    def price_comm_moves(self, v: int, dst: int, ts: np.ndarray) -> np.ndarray:
        """Fused-window twin of ``schedule_front.price_comm_moves``: a
        one-row ``price_comm_batch``."""
        (deltas,) = self.price_comm_batch([(v, dst, int(ts[0]), len(ts))])
        return deltas

    def price_comp_moves(self, v: int, p: int, ts: np.ndarray) -> np.ndarray:
        """Fused-window twin of ``schedule_front.price_comp_moves``."""
        with span("windows.price", kind="comp"):
            sched = self.sched
            s = sched.assign[v][p]
            om = sched.inst.dag.omega[v]
            lo, W = int(ts[0]), len(ts)
            out = self._price_windows("comp", [(lo, p, 0, int(om))], W, 1)
            w1_minus = sched._kind_max_if("work", s, p, -om)
            d_s = (sched._step_cost(w1_minus, sched.h_of(s))
                   - sched._scost[s])
            deltas = d_s + out[0, :W].astype(np.float64)
            if lo <= s < lo + W:
                deltas[s - lo] = 0.0
            return deltas

    def price_node_moves(self, v: int) -> np.ndarray:
        """Fused twin of ``schedule_front.price_node_moves``: the per-
        superstep (P x P) delta matrices fold on device in one program.
        Falls back to the numpy front when few supersteps are touched."""
        from ..core.frontier.schedule_front import price_node_moves
        sched = self.sched
        P = self.P
        (p, _), = sched.assign[v].items()
        cells = _node_move_cells(sched, v)
        if len(cells) < DEVICE_MIN_STEPS:
            return price_node_moves(sched, v)
        with span("windows.price", kind="node"):
            if self._dirty:
                self._refresh()
            steps = sorted(cells)
            T = len(steps)
            Tp = _pow2(T)
            ts = np.zeros(Tp, dtype=np.int32)
            ts[:T] = steps
            dw = np.zeros((Tp, P, P), dtype=np.int32)
            ds = np.zeros((Tp, P, P), dtype=np.int32)
            dr = np.zeros((Tp, P, P), dtype=np.int32)
            for i, t in enumerate(steps):
                w, se, r = cells[t]
                dw[i], ds[i], dr[i] = w, se, r
            fn = _node_program(Tp, self.L, self.g)
            out = fn(self._work, self._sent, self._recv, self._scost,
                     self._put(ts), self._put(dw), self._put(ds),
                     self._put(dr))
            self._tally("syncs", 1)
            with span("windows.wait"):
                deltas = np.asarray(out, dtype=np.float64)
            deltas[p] = 0.0
            return deltas


@functools.lru_cache(maxsize=_PROGRAM_CACHE_SIZE)
def _win_program(kind: str, Kp: int, Wp: int, L: int, g: int):
    """The ``"comm"`` or ``"comp"`` window pricer of ``Kp`` windows of
    ``Wp`` supersteps each, for BSP parameters ``L`` and ``g`` (both
    closed over).  Row k of ``wins`` is ``(lo, a, b, weight)``: window k
    starts at superstep ``lo``; a comm moves ``weight`` = mu from
    processor a to b, a compute re-times ``weight`` = omega on processor
    a.  Returns the (Kp, Wp) int32 insertion deltas."""

    def step_cost(w1, h):
        return jnp.where(h >= 1, w1 + L + g * h, w1)

    def win(rows, recv, stop, rtop, wtop, scost, wins):
        lo, a, b, wt = (wins[:, k, None] for k in range(4))
        idx = jnp.clip(lo + jnp.arange(Wp), 0, rows.shape[0] - 1)
        if kind == "comm":      # rows are the sent rows
            s_alt = jnp.where(stop[idx, 1] == a, stop[idx, 2],
                              stop[idx, 0])
            r_alt = jnp.where(rtop[idx, 1] == b, rtop[idx, 2],
                              rtop[idx, 0])
            h = jnp.maximum(jnp.maximum(s_alt, rows[idx, a] + wt),
                            jnp.maximum(r_alt, recv[idx, b] + wt))
            return step_cost(wtop[idx, 0], h) - scost[idx]
        # comp: rows are the work rows
        w_alt = jnp.where(wtop[idx, 1] == a, wtop[idx, 2], wtop[idx, 0])
        w1 = jnp.maximum(w_alt, rows[idx, a] + wt)
        h = jnp.maximum(stop[idx, 0], rtop[idx, 0])
        return step_cost(w1, h) - scost[idx]

    return jax.jit(win)


@functools.lru_cache(maxsize=_PROGRAM_CACHE_SIZE)
def _node_program(Tp: int, L: int, g: int):
    """The node-move (P x P) delta fold over ``Tp`` touched supersteps for
    BSP parameters ``L`` and ``g`` (both closed over)."""

    def fold(work, sent, recv, scost, ts, dw, ds, dr):
        # ts: (Tp,) touched steps; d*: (Tp, P, P) candidate x processor
        w1 = (work[ts][:, None, :] + dw).max(axis=2)
        s1 = (sent[ts][:, None, :] + ds).max(axis=2)
        r1 = (recv[ts][:, None, :] + dr).max(axis=2)
        h = jnp.maximum(s1, r1)
        step = jnp.where(h >= 1, w1 + L + g * h, w1)
        return (step - scost[ts][:, None]).sum(axis=0)

    return jax.jit(fold)


_PROGRAM_CACHES = {"find": find_program, "apply": _apply_program,
                   "win": _win_program, "node": _node_program}


def program_cache_stats() -> dict:
    """Hit/miss/size counters of the per-signature program caches, in the
    shape of ``gain.kernel_cache_stats``: ``misses`` counts the distinct
    signatures built, ``hits`` every attach or pricer that reused one."""
    out = {}
    for name, fn in _PROGRAM_CACHES.items():
        info = fn.cache_info()
        out[name] = {"hits": info.hits, "misses": info.misses,
                     "size": info.currsize, "maxsize": info.maxsize}
    return out


def _node_move_cells(sched, v: int) -> dict:
    """Per-superstep (work, sent, recv) (P, P) int delta matrices of the
    compound node move -- the same cells ``price_node_moves`` accumulates,
    in the same fill order (int32; caller guarantees integral weights)."""
    P = sched.inst.P
    (p, s), = sched.assign[v].items()
    dag = sched.inst.dag
    mu, om = int(dag.mu[v]), int(dag.omega[v])
    allq = np.arange(P)
    cells: dict[int, list] = {}

    def at(t):
        got = cells.get(t)
        if got is None:
            got = [np.zeros((P, P), dtype=np.int32) for _ in range(3)]
            cells[t] = got
        return got

    for dst in sorted(sched.src_index.get((v, p), ())):
        _, t = sched.comms[(v, dst)]
        _, se, r = at(t)
        se[:, p] -= mu
        r[dst, dst] -= mu
        keep = allq != dst
        se[allq[keep], allq[keep]] += mu
    for q in range(P):
        c0 = sched.comms.get((v, q))
        if c0 is not None and c0[0] != p:
            src0, t0 = c0
            at(t0)[1][q, src0] -= mu
            at(t0)[2][q, q] -= mu
    w = at(s)[0]
    w[:, p] -= om
    w[allq, allq] += om
    uses_p = sched.uses_on(v, p)
    if uses_p:
        tf = min(uses_p) - 1
        at(tf)[1][allq, allq] += mu
        at(tf)[2][:, p] += mu
    return cells
