"""Logical-axis sharding rules and activation constraints.

Parameters carry *logical* axes implied by their path names; `param_spec`
maps them to mesh axes with divisibility guards (a dimension is sharded on
'model' only when divisible; otherwise replicated -- e.g. 8 KV heads on a
16-way model axis are replicated, the standard fallback).

Activation constraints (`constrain`) are no-ops outside a mesh context so
the same model code runs on a single CPU device and under pjit on 512
devices.
"""
from __future__ import annotations

import re
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_ACTIVE_MESH: Mesh | None = None


def set_active_mesh(mesh: Mesh | None) -> None:
    global _ACTIVE_MESH
    _ACTIVE_MESH = mesh


def active_mesh() -> Mesh | None:
    return _ACTIVE_MESH


def batch_axes() -> tuple:
    """Mesh axes the global batch is sharded over."""
    if _ACTIVE_MESH is None:
        return ()
    names = _ACTIVE_MESH.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def constrain(x, *spec):
    """with_sharding_constraint that degrades to a no-op without a mesh."""
    if _ACTIVE_MESH is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_ACTIVE_MESH, P(*spec)))


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return mesh.shape[axis]


def _guard(mesh: Mesh, shape: tuple, spec: list) -> P:
    """Drop mesh axes that don't divide the corresponding dim."""
    out = []
    for dim, axis in zip(shape, spec):
        if axis is None:
            out.append(None)
        elif dim % _axis_size(mesh, axis) == 0:
            out.append(axis)
        else:
            out.append(None)
    return P(*out)


# path-pattern -> which dim gets the 'model' axis (negative = from the end)
_MODEL_DIM_RULES: list[tuple[str, int]] = [
    (r"embed$", 0),            # (vocab, d) -> shard vocab
    (r"lm_head$", -1),         # (d, vocab) -> shard vocab
    (r"\bwq$", -1), (r"\bwk$", -1), (r"\bwv$", -1),   # (.., d, H*hd)
    (r"\bwo$", -2),            # (.., H*hd, d)
    (r"\bw_gate$", -1), (r"\bw_up$", -1),             # (.., d, f)
    (r"\bw_down$", -2),        # (.., f, d)
    (r"\be_gate$", -3), (r"\be_up$", -3), (r"\be_down$", -3),  # (L,E,..,..)
    (r"\brouter$", -1),
    (r"\bwq_b$", -1), (r"\bwkv_b$", -1),              # MLA head projections
    (r"\bmla_wo$", -2),
    (r"\bin_proj$", -1),       # mamba (d, 2*di)
    (r"\bconv_w$", -2), (r"\bA_log$", -2), (r"\bssm_D$", -1),
    (r"\bx_proj$", -2), (r"\bdt_proj$", -1), (r"\bout_proj$", -2),
    (r"\bcross_wq$", -1), (r"\bcross_wk$", -1), (r"\bcross_wv$", -1),
    (r"\bcross_wo$", -2),
]


def param_spec(path: str, shape: tuple, strategy: str = "tp") -> P:
    """PartitionSpec for a parameter identified by its tree path."""
    mesh = _ACTIVE_MESH
    if mesh is None or strategy == "dp_seq" or "model" not in mesh.axis_names:
        return P()
    for pat, dim in _MODEL_DIM_RULES:
        if re.search(pat, path):
            spec = [None] * len(shape)
            spec[dim if dim >= 0 else len(shape) + dim] = "model"
            # 'tp+ep_data': expert FFN weights additionally sharded over
            # the data axis on dim -2 (persistent storage /dp; gathered
            # per layer at the shard_map boundary) -- needed to fit
            # deepseek-v3 on v5e HBM.
            if ("ep_data" in strategy and "data" in mesh.axis_names
                    and re.search(r"\be_(gate|up|down)$", path)):
                spec[len(shape) - 2] = "data"
            return _guard(mesh, shape, spec)
    return P()


def tree_param_specs(params: Any, strategy: str = "tp") -> Any:
    """Map a params pytree to PartitionSpecs using joined key paths."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat:
        name = "/".join(_key_str(k) for k in path)
        specs.append(param_spec(name, np.shape(leaf), strategy))
    return jax.tree_util.tree_unflatten(treedef, specs)


def tree_shardings(params: Any, mesh: Mesh, strategy: str = "tp") -> Any:
    specs = tree_param_specs(params, strategy)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)
