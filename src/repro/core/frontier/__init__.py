"""Frontier-pricing layer: batched candidate-front evaluation (PR 3).

Both search stacks price enormous numbers of candidate moves; PR 1/PR 2
made each *single* pricing incremental, this package makes whole *fronts*
of candidates one vectorized evaluation:

  * ``partition_front`` -- ragged batched gain evaluation over the CSR
    arrays of a ``PartitionState`` (NumPy), plus the ``GainCache`` that
    makes FM / replication passes output-sensitive (only nodes whose gain
    changed are repriced), and ``device_pass``, the jax backend's
    device-resident pass (``repro.kernels.front_pass``);
  * ``schedule_front`` -- batched node-move pricing and superstep-
    replication front enumeration + pure pricing against flat
    per-superstep load arrays of a ``ScheduleState``.

Pricing here is *bit-equal* to the scalar engine deltas
(``PartitionState.delta_masks`` / ``ScheduleState.delta_node_move``); the
heuristics keep their exact decision rules, so refactoring onto this layer
changes wall-clock, not results (the one deliberate exception is the SR
pass's commit-the-winner rule, applied to engine and oracle in lockstep).
"""
from .partition_front import (GainCache, add_replica_candidates,
                              connected_add_candidates, connected_targets,
                              device_pass, fm_move_candidates, get_backend,
                              lookahead_window, move_candidates,
                              price_mask_front, refresh_boundary_window,
                              set_backend)
from .schedule_front import (apply_sm_mutations, apply_sr_mutations,
                             commit_superstep_merge,
                             commit_superstep_replication,
                             commit_superstep_split, device_windows,
                             node_move_targets, price_comm_moves,
                             price_comp_moves, price_node_moves,
                             price_superstep_merge,
                             price_superstep_replication,
                             price_superstep_split, sm_front, split_front,
                             sr_front)

__all__ = [
    "GainCache", "add_replica_candidates", "connected_add_candidates",
    "connected_targets", "device_pass", "fm_move_candidates", "get_backend",
    "lookahead_window", "move_candidates", "price_mask_front",
    "refresh_boundary_window", "set_backend",
    "apply_sm_mutations", "apply_sr_mutations", "commit_superstep_merge",
    "commit_superstep_replication", "commit_superstep_split",
    "device_windows", "node_move_targets",
    "price_comm_moves", "price_comp_moves", "price_node_moves",
    "price_superstep_merge", "price_superstep_replication",
    "price_superstep_split", "sm_front", "split_front", "sr_front",
]
