"""Mixture-of-Experts layer with replication-aware expert placement.

This is where the paper's contribution lands in the runtime.  The paper's
moe-8 benchmark *is* expert co-activation partitioning for serving (§3.2):
hyperedges = frequently co-invoked expert 8-tuples, processors = devices,
and replication lets hot experts live on several devices so tokens reach
all their experts with fewer cross-device hops (the (lambda_e - 1) metric).

TPU adaptation (DESIGN.md §3): experts are sharded over the 'model' mesh
axis ("EP shards").  A ``PlacementPlan`` maps physical *slots* (shard,
slot) -> expert; replication = an expert occupying slots on several shards.

  * training / prefill (`mode='a2a'`): tokens are sequence-sharded over the
    model axis; each token-choice either hits a *local* replica (free) or
    is sent through a static-capacity all_to_all.  Replication-aware
    placement raises the local fraction, which statically shrinks the
    all_to_all buffers -- the communication saving of the paper, visible in
    HLO collective bytes.
  * decode (`mode='tp'`): tokens are replicated across the model axis; each
    shard computes its slots and results are psum-combined.
  * no mesh: dense single-device reference.

Dispatch is sort-based (argsort by slot + static-capacity buffers), not
one-hot einsum: at E=256 the (T,E,C) dispatch matmuls would dwarf the
expert FLOPs.  Training uses the no-replication plan (replicated slots
would need gradient tying); serving transforms weights into the replicated
slot layout (`materialize_slots`) -- mirroring the paper's decode-phase
setting (§B.1).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..parallel.sharding import active_mesh, batch_axes
from .config import ModelConfig


@dataclasses.dataclass(frozen=True)
class PlacementPlan:
    """Static expert->device placement with replication."""
    n_experts: int
    n_shards: int
    slots_per_shard: int
    slot_expert: tuple   # (n_shards, slots_per_shard); -1 = empty slot
    local_slot: tuple    # (n_shards, n_experts): local slot id or -1
    home_shard: tuple    # (n_shards, n_experts): dest shard when remote
    home_slot: tuple     # (n_shards, n_experts): slot id on dest shard
    local_fraction: float
    capacity_factor: float = 1.25

    def arrays(self):
        return (np.array(self.slot_expert, np.int32),
                np.array(self.local_slot, np.int32),
                np.array(self.home_shard, np.int32),
                np.array(self.home_slot, np.int32))

    @property
    def total_slots(self) -> int:
        return self.n_shards * self.slots_per_shard

    def replicas(self, e: int) -> int:
        return int(sum(1 for row in self.local_slot if row[e] >= 0))


def _finalize_plan(shard_slots, n_experts, n_shards, expert_freq,
                   capacity_factor):
    sps = max(len(s) for s in shard_slots)
    slot_expert = -np.ones((n_shards, sps), np.int64)
    local_slot = -np.ones((n_shards, n_experts), np.int64)
    for p, slots in enumerate(shard_slots):
        for i, e in enumerate(slots):
            slot_expert[p, i] = e
            local_slot[p, e] = i
    home_shard = np.zeros((n_shards, n_experts), np.int64)
    home_slot = np.zeros((n_shards, n_experts), np.int64)
    for e in range(n_experts):
        replicas = [p for p in range(n_shards) if local_slot[p, e] >= 0]
        if not replicas:
            raise ValueError(f"expert {e} unplaced")
        for m in range(n_shards):
            best = min(replicas, key=lambda r: min((r - m) % n_shards,
                                                   (m - r) % n_shards))
            home_shard[m, e] = best
            home_slot[m, e] = local_slot[best, e]
    freq = np.ones(n_experts) if expert_freq is None else np.asarray(
        expert_freq, np.float64)
    freq = freq / max(freq.sum(), 1e-9)
    local_fraction = float(sum(
        freq[e] * (np.sum(local_slot[:, e] >= 0) / n_shards)
        for e in range(n_experts)))
    return PlacementPlan(
        n_experts=n_experts, n_shards=n_shards, slots_per_shard=sps,
        slot_expert=tuple(map(tuple, slot_expert.tolist())),
        local_slot=tuple(map(tuple, local_slot.tolist())),
        home_shard=tuple(map(tuple, home_shard.tolist())),
        home_slot=tuple(map(tuple, home_slot.tolist())),
        local_fraction=local_fraction,
        capacity_factor=capacity_factor,
    )


def round_robin_plan(n_experts: int, n_shards: int,
                     capacity_factor: float = 1.25) -> PlacementPlan:
    """No replication: expert e on shard e % n_shards (the baseline)."""
    shard_slots = [[] for _ in range(n_shards)]
    for e in range(n_experts):
        shard_slots[e % n_shards].append(e)
    return _finalize_plan(shard_slots, n_experts, n_shards, None,
                          capacity_factor)


def plan_from_masks(masks, n_experts: int, n_shards: int,
                    expert_freq=None,
                    capacity_factor: float = 1.25) -> PlacementPlan:
    """Plan from partitioner output ``masks`` (bit p of masks[e] = replica
    of expert e on shard p) -- the solution of hypergraph partitioning with
    replication on the co-activation hypergraph."""
    shard_slots = [[] for _ in range(n_shards)]
    for e in range(n_experts):
        m = int(masks[e])
        for p in range(n_shards):
            if (m >> p) & 1:
                shard_slots[p].append(e)
    return _finalize_plan(shard_slots, n_experts, n_shards, expert_freq,
                          capacity_factor)


def migration_bytes(old_plan: PlacementPlan, new_plan: PlacementPlan,
                    bytes_per_expert: int) -> int:
    """Weight bytes that must move to transition ``old_plan -> new_plan``.

    An expert's weights are copied onto every shard that hosts it in the
    new plan but did not in the old one (dropping a replica is free).  This
    is the cost side of the online controller's commit gate.
    """
    if (old_plan.n_experts != new_plan.n_experts
            or old_plan.n_shards != new_plan.n_shards):
        raise ValueError("plans cover different expert/shard spaces")
    old = np.asarray(old_plan.local_slot) >= 0   # (P, E) replica present
    new = np.asarray(new_plan.local_slot) >= 0
    return int(np.count_nonzero(new & ~old)) * int(bytes_per_expert)


def a2a_capacities(plan: PlacementPlan, T_loc: int, top_k: int):
    """Static buffer capacities of the a2a path (shared with the roofline
    cost model so analysis costs exactly what the implementation runs)."""
    n_sh = plan.n_shards
    loc_frac = max(plan.local_fraction, 1.0 / n_sh)
    cap_local = max(1, int(np.ceil(
        T_loc * top_k * loc_frac / plan.slots_per_shard
        * plan.capacity_factor * 2)))
    cap_send = max(1, int(np.ceil(
        T_loc * top_k * (1.0 - loc_frac) / n_sh * plan.capacity_factor)))
    cap_in = max(1, int(np.ceil(
        n_sh * cap_send / plan.slots_per_shard * 2)))
    return cap_local, cap_send, cap_in


# ------------------------------------------------------------------ routing

def router_topk(router_w, x: jax.Array, cfg: ModelConfig):
    """x: (T, D) -> weights (T, k), experts (T, k), aux loss scalar."""
    logits = jnp.einsum("td,de->te", x, router_w,
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, cfg.top_k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    E = cfg.n_experts
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=1), axis=0)
    aux = E * jnp.sum(me * ce)
    return w.astype(x.dtype), idx, aux


def sort_dispatch(xt: jax.Array, slot_ids: jax.Array, keep: jax.Array,
                  n_slots: int, capacity: int):
    """Static-shape sparse dispatch.

    xt: (T, D); slot_ids/keep: (T, k).  Returns
      xin:      (n_slots, capacity, D)  tokens grouped per slot (drops over
                                        capacity, standard MoE semantics)
      buf_of:   (T, k) int32            buffer row of each choice, or -1
    """
    T, k = slot_ids.shape
    D = xt.shape[-1]
    flat = jnp.where(keep, slot_ids, n_slots).reshape(-1)       # (T*k,)
    order = jnp.argsort(flat, stable=True)
    sorted_slot = flat[order]
    starts = jnp.searchsorted(sorted_slot, jnp.arange(n_slots + 1),
                              side="left")
    pos = jnp.arange(T * k) - starts[jnp.clip(sorted_slot, 0, n_slots)]
    ok = (sorted_slot < n_slots) & (pos < capacity)
    buf_sorted = jnp.where(ok, sorted_slot * capacity + pos,
                           n_slots * capacity)                  # dump row
    # invert the permutation to index by original (t, k)
    buf_flat = jnp.zeros(T * k, jnp.int32).at[order].set(
        buf_sorted.astype(jnp.int32))
    token_of_row = jnp.full(n_slots * capacity + 1, T, jnp.int32)
    token_of_row = token_of_row.at[buf_sorted].set(
        (order // k).astype(jnp.int32), mode="drop")
    xin = jnp.take(jnp.concatenate([xt, jnp.zeros((1, D), xt.dtype)], 0),
                   jnp.minimum(token_of_row[:-1], T), axis=0)
    xin = jnp.where((token_of_row[:-1] < T)[:, None], xin, 0)
    xin = xin.reshape(n_slots, capacity, D)
    buf_of = jnp.where(buf_flat < n_slots * capacity, buf_flat, -1)
    return xin, buf_of.reshape(T, k)


def combine_from_buffers(yout_flat: jax.Array, buf_of: jax.Array,
                         w: jax.Array) -> jax.Array:
    """yout_flat: (rows, D); buf_of: (T,k) row ids (-1 dropped); w: (T,k)."""
    D = yout_flat.shape[-1]
    pad = jnp.concatenate([yout_flat, jnp.zeros((1, D), yout_flat.dtype)], 0)
    gathered = pad[jnp.where(buf_of >= 0, buf_of, pad.shape[0] - 1)]  # (T,k,D)
    gathered = jnp.where((buf_of >= 0)[..., None], gathered, 0)
    return jnp.einsum("tkd,tk->td", gathered, w)


def _expert_ffn(e_gate, e_up, e_down, xin: jax.Array) -> jax.Array:
    """xin: (n_slots, C, D) -> (n_slots, C, D) through per-slot SwiGLU."""
    g = jnp.einsum("scd,sdf->scf", xin, e_gate)
    u = jnp.einsum("scd,sdf->scf", xin, e_up)
    return jnp.einsum("scf,sfd->scd", jax.nn.silu(g) * u, e_down)


# ---------------------------------------------------------------- execution

def moe_dense_ref(p: dict, x: jax.Array, cfg: ModelConfig):
    """Single-device reference: dense top-k MoE (tests / tiny configs)."""
    B, S, D = x.shape
    xt = x.reshape(-1, D)
    w, idx, aux = router_topk(p["router"], xt, cfg)
    g = jnp.einsum("td,edf->tef", xt, p["e_gate"])
    u = jnp.einsum("td,edf->tef", xt, p["e_up"])
    h = jax.nn.silu(g) * u
    y = jnp.einsum("tef,efd->ted", h, p["e_down"])
    oh = jax.nn.one_hot(idx, cfg.n_experts, dtype=x.dtype)
    gates = jnp.einsum("tk,tke->te", w, oh)
    out = jnp.einsum("ted,te->td", y, gates)
    if "w_gate" in p:
        from .layers import swiglu
        out = out + swiglu(p, x).reshape(-1, D)
    return out.reshape(B, S, D), aux


def moe_tp(p: dict, x: jax.Array, cfg: ModelConfig, plan: PlacementPlan):
    """Tokens replicated over the model axis; each shard computes its
    slots; psum combine.  Used for decode (tiny token counts)."""
    mesh = active_mesh()
    B, S, D = x.shape
    _, local_slot, _, _ = plan.arrays()
    dp = batch_axes()
    all_axes = tuple(dp) + ("model",)
    dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    B_loc = B // dp_size
    T_loc = B_loc * S
    cap = max(1, int(np.ceil(T_loc * cfg.top_k / plan.total_slots
                             * plan.capacity_factor * plan.n_shards)))

    def per_shard(xl, e_gate, e_up, e_down, router):
        m = jax.lax.axis_index("model")
        xt = xl.reshape(-1, D)
        w, idx, aux = router_topk(router, xt, cfg)
        slots = jnp.asarray(local_slot)[m][idx]
        keep = slots >= 0
        xin, buf_of = sort_dispatch(xt, jnp.maximum(slots, 0), keep,
                                    plan.slots_per_shard, cap)
        yout = _expert_ffn(e_gate, e_up, e_down, xin)
        y = combine_from_buffers(yout.reshape(-1, D), buf_of, w)
        y = jax.lax.psum(y, "model")
        if dp:  # aux is invariant over 'model' here (tokens replicated)
            aux = jax.lax.pmean(aux, dp)
        return y.reshape(B_loc, S, D), aux

    y, aux = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(dp or None, None, None), P("model"), P("model"),
                  P("model"), P()),
        out_specs=(P(dp or None, None, None), P()),
    )(x, p["e_gate_slots"], p["e_up_slots"], p["e_down_slots"], p["router"])
    if "w_gate" in p:
        from .layers import swiglu
        y = y + swiglu(p, x)
    return y, aux


def moe_a2a(p: dict, x: jax.Array, cfg: ModelConfig, plan: PlacementPlan):
    """Sequence-sharded tokens + static-capacity all_to_all dispatch.
    Local replicas bypass the all_to_all entirely: the plan's expected
    locality statically sizes (shrinks) the communication buffers."""
    mesh = active_mesh()
    B, S, D = x.shape
    _, local_slot, home_shard, home_slot = plan.arrays()
    n_sh = plan.n_shards
    dp = batch_axes()
    all_axes = tuple(dp) + ("model",)
    dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    B_loc, S_loc = B // dp_size, S // n_sh
    T_loc = B_loc * S_loc
    cap_local, cap_send, cap_in = a2a_capacities(plan, T_loc, cfg.top_k)

    def per_shard(xl, e_gate, e_up, e_down, router):
        m = jax.lax.axis_index("model")
        xt = xl.reshape(-1, D)
        w, idx, aux = router_topk(router, xt, cfg)
        my_local = jnp.asarray(local_slot)[m][idx]        # (T,k)
        is_local = my_local >= 0
        # ---- local replicas: no communication (the replication win) ----
        xin_l, buf_l = sort_dispatch(xt, jnp.maximum(my_local, 0), is_local,
                                     plan.slots_per_shard, cap_local)
        # ---- remote dispatch through all_to_all ----
        dest = jnp.asarray(home_shard)[m][idx]
        dslot = jnp.asarray(home_slot)[m][idx]
        send_x, buf_r = sort_dispatch(xt, dest, ~is_local, n_sh, cap_send)
        # ship each row's target slot id alongside (int payload)
        slot_payload = jnp.full((n_sh * cap_send,), -1, jnp.int32)
        slot_payload = slot_payload.at[
            jnp.where(buf_r >= 0, buf_r, n_sh * cap_send).reshape(-1)
        ].set(dslot.reshape(-1).astype(jnp.int32), mode="drop")
        recv_x = jax.lax.all_to_all(send_x, "model", 0, 0)
        recv_slot = jax.lax.all_to_all(
            slot_payload.reshape(n_sh, cap_send, 1), "model", 0, 0)[..., 0]
        rx = recv_x.reshape(-1, D)
        rslot = recv_slot.reshape(-1)
        xin_r, buf_in = sort_dispatch(rx, jnp.maximum(rslot, 0)[:, None],
                                      (rslot >= 0)[:, None],
                                      plan.slots_per_shard, cap_in)
        # ---- expert FFN ----
        yout_l = _expert_ffn(e_gate, e_up, e_down, xin_l)
        yout_r = _expert_ffn(e_gate, e_up, e_down, xin_r)
        # ---- combine: local directly, remote via return all_to_all ----
        y = combine_from_buffers(yout_l.reshape(-1, D), buf_l, w * is_local)
        ret = combine_from_buffers(
            yout_r.reshape(-1, D), buf_in,
            jnp.ones_like(buf_in, dtype=xt.dtype))          # (n_sh*cap_send, D)
        ret = jax.lax.all_to_all(ret.reshape(n_sh, cap_send, D), "model", 0, 0)
        y = y + combine_from_buffers(ret.reshape(-1, D), buf_r,
                                     w * (~is_local))
        aux = jax.lax.pmean(aux, all_axes)
        return y.reshape(B_loc, S_loc, D), aux

    y, aux = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(dp or None, "model", None), P("model"), P("model"),
                  P("model"), P()),
        out_specs=(P(dp or None, "model", None), P()),
    )(x, p["e_gate_slots"], p["e_up_slots"], p["e_down_slots"], p["router"])
    if "w_gate" in p:
        from .layers import swiglu
        y = y + swiglu(p, x)
    return y, aux


def moe_apply(p: dict, x: jax.Array, cfg: ModelConfig, plan: PlacementPlan,
              mode: str):
    """mode: 'a2a' (train/prefill), 'tp' (decode), 'dense' (no mesh)."""
    if active_mesh() is None or mode == "dense":
        return moe_dense_ref(p, x, cfg)
    p = materialize_slots(p, plan)
    if mode == "tp":
        return moe_tp(p, x, cfg, plan)
    return moe_a2a(p, x, cfg, plan)


def materialize_slots(p: dict, plan: PlacementPlan) -> dict:
    """Gather logical expert weights (..., E, D, F) into the physical slot
    layout (..., n_shards*slots_per_shard, D, F).  Differentiable (training
    gradients of replicated slots sum back into the logical expert)."""
    if "e_gate_slots" in p:
        return p
    slot_expert = np.array(plan.slot_expert, np.int64).reshape(-1)
    gather = np.maximum(slot_expert, 0)

    def take(wname):
        return jnp.take(p[wname], jnp.asarray(gather), axis=-3)

    out = dict(p)
    out["e_gate_slots"] = take("e_gate")
    out["e_up_slots"] = take("e_up")
    out["e_down_slots"] = take("e_down")
    return out
