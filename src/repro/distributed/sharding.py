"""Path-based sharding rules: parameter/activation/cache PartitionSpecs.

The model zoo names its leaves canonically (``q_proj``, ``expert_w_gate``,
``ssm_in_proj``, ...), so a small rule table assigns the tensor-parallel
('model') dim per leaf kind, and a generic FSDP pass shards the largest
remaining divisible dim over the data axes.  Anything non-divisible falls
back gracefully (fewer axes -> replicated), so every mesh shape compiles.

Mesh axes: ('data', 'model') single pod, ('pod', 'data', 'model') multi-pod
(DESIGN.md §5).  ``data_axes(mesh)`` returns ('pod','data') or ('data',).
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


# ---------------------------------------------------------------------------
# Sharding strategy (hillclimb lever, EXPERIMENTS.md §Perf):
#   '2d'   — FSDP over data axes x tensor-parallel over 'model' (default)
#   'fsdp' — params fully sharded over ALL axes, batch over ALL axes, no TP
#            (collective-optimal for models whose activations >> params)
#   'dp'   — replicated params, batch over all axes (tiny models)
# ---------------------------------------------------------------------------
_STRATEGY = "2d"


def set_strategy(s: str):
    global _STRATEGY
    assert s in ("2d", "fsdp", "dp"), s
    _STRATEGY = s


def get_strategy() -> str:
    return _STRATEGY


def all_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([mesh.shape[a] for a in axes]))


# leaf-name -> index of the dim to shard over 'model' (negative ok).
# Stacked layer params carry a leading layer axis handled separately.
_TP_DIM = {
    "q_proj": -1,
    "k_proj": -1,
    "v_proj": -1,
    "g_proj": -1,
    "o_proj": -2,
    "gate_proj": -1,
    "up_proj": -1,
    "down_proj": -2,
    "cm_k_proj": -1,
    "cm_v_proj": -2,
    "cm_r_proj": -1,
    "w_lora_a": -1,
    "w_lora_b": -1,
    "r_proj": -1,
    "expert_w_gate": 0,  # expert-parallel
    "expert_w_up": 0,
    "expert_w_down": 0,
    "ssm_in_proj": -1,
    "ssm_out_proj": -2,
    "embed": 0,  # vocab
    "unembed": -1,  # vocab
}


def _leaf_name(path) -> str:
    for entry in reversed(path):
        if isinstance(entry, jax.tree_util.DictKey):
            return str(entry.key)
    return ""


def _is_stacked(path) -> bool:
    return any(
        isinstance(e, jax.tree_util.DictKey) and str(e.key) in ("layers", "enc_layers", "dec_layers")
        for e in path
    )


def param_spec(path, shape: tuple[int, ...], mesh: Mesh, strategy: str | None = None) -> P:
    """PartitionSpec for one parameter leaf."""
    strategy = strategy or get_strategy()
    name = _leaf_name(path)
    stacked = _is_stacked(path)
    ndim = len(shape)
    spec: list[Any] = [None] * ndim
    lead = 1 if stacked else 0  # skip the layer-stack axis

    if strategy == "dp":
        return P(*spec)

    if strategy == "fsdp":
        # experts stay expert-parallel over 'model' (gathering every expert
        # per device would be infeasible); everything else fully sharded
        if name.startswith("expert_w") and shape[lead] % mesh.shape["model"] == 0:
            spec[lead] = "model"
            da = data_axes(mesh)
            dsize = _axis_size(mesh, da)
            cand = [
                i for i in range(lead + 1, ndim)
                if shape[i] % dsize == 0 and shape[i] >= dsize
            ]
            if cand and dsize > 1:
                best = max(cand, key=lambda i: shape[i])
                spec[best] = da if len(da) > 1 else da[0]
            return P(*spec)
        # fully shard the largest divisible dim over as many axes as divide it
        for axes in (all_axes(mesh), data_axes(mesh) + ("model",), ("model",), data_axes(mesh)):
            axes = tuple(a for a in axes if a in mesh.axis_names)
            size = _axis_size(mesh, axes)
            if size <= 1:
                continue
            cand = [i for i in range(lead, ndim) if shape[i] % size == 0 and shape[i] >= size]
            if cand:
                best = max(cand, key=lambda i: shape[i])
                spec[best] = axes if len(axes) > 1 else axes[0]
                return P(*spec)
        return P(*spec)

    # --- '2d' (default): TP + FSDP -----------------------------------------
    # 1) tensor-parallel dim (negative = from the end; positive = after the
    #    layer-stack axis, e.g. the expert dim of stacked MoE weights)
    tp = _TP_DIM.get(name)
    if tp is not None and ndim - lead >= 2:
        idx = (ndim + tp) if tp < 0 else (tp + lead)
        if lead <= idx < ndim and shape[idx] % mesh.shape["model"] == 0:
            spec[idx] = "model"

    # 2) FSDP: largest remaining divisible dim over the data axes
    da = data_axes(mesh)
    dsize = _axis_size(mesh, da)
    if dsize > 1 and ndim - lead >= 1:
        candidates = [
            i for i in range(lead, ndim) if spec[i] is None and shape[i] % dsize == 0
        ]
        if candidates:
            best = max(candidates, key=lambda i: shape[i])
            if shape[best] >= dsize:  # don't shard tiny dims
                spec[best] = da if len(da) > 1 else da[0]
    return P(*spec)


def param_shardings(params, mesh: Mesh):
    """Pytree of NamedShardings matching ``params`` (works on ShapeDtypeStructs)."""

    def one(path, leaf):
        return NamedSharding(mesh, param_spec(path, leaf.shape, mesh))

    return jax.tree_util.tree_map_with_path(one, params)


# ---------------------------------------------------------------------------
# activations / batch / cache
# ---------------------------------------------------------------------------
def batch_spec(mesh: Mesh, batch: int, strategy: str | None = None) -> P:
    """Shard the batch dim over the data axes ('2d') or all axes ('fsdp'/'dp')."""
    strategy = strategy or get_strategy()
    axes = data_axes(mesh) if strategy == "2d" else all_axes(mesh)
    use = []
    rem = batch
    for a in axes:
        if rem % mesh.shape[a] == 0:
            use.append(a)
            rem //= mesh.shape[a]
    if not use:
        return P(None)
    return P(tuple(use) if len(use) > 1 else use[0])


def token_sharding(mesh: Mesh, batch: int) -> NamedSharding:
    return NamedSharding(mesh, P(*batch_spec(mesh, batch), None))


def cache_spec(path, shape: tuple[int, ...], mesh: Mesh, batch: int) -> P:
    """KV/SSM cache sharding.

    KV caches (L, B, Hkv, S, hd): batch over data axes; the cache *sequence*
    over 'model' (flash-decoding style sequence parallelism — kv-head counts
    are below the TP width for every assigned arch).  For global_batch=1
    (long_500k) the sequence is sharded over data axes too.
    SSM/conv/wkv states: batch over data; feature dims over 'model' when
    divisible.
    """
    name = _leaf_name(path)
    da = data_axes(mesh)
    spec: list[Any] = [None] * len(shape)

    # locate the batch dim: caches are stacked (layer axis 0), batch axis 1;
    # whisper cross-cache 'ck'/'cv' share the same layout.
    bdim = 1 if len(shape) >= 2 else 0
    bspec = batch_spec(mesh, batch)[0]
    if shape[bdim] == batch and bspec is not None:
        spec[bdim] = bspec

    if name in ("k", "v", "ck", "cv") and len(shape) == 5:
        # (L, B, Hkv, S, hd): shard S over 'model' (+ data axes if batch=1)
        s_axes = ("model",) + (da if spec[bdim] is None else ())
        use: list[str] = []
        for a in s_axes:
            if shape[3] % _axis_size(mesh, tuple(use) + (a,)) == 0:
                use.append(a)
        if use:
            spec[3] = tuple(use) if len(use) > 1 else use[0]
    else:
        # states: shard the largest trailing dim over 'model' when divisible
        for i in range(len(shape) - 1, bdim, -1):
            if spec[i] is None and shape[i] % mesh.shape["model"] == 0 and shape[i] >= mesh.shape["model"]:
                spec[i] = "model"
                break
    return P(*spec)


def cache_shardings(cache, mesh: Mesh, batch: int):
    def one(path, leaf):
        return NamedSharding(mesh, cache_spec(path, leaf.shape, mesh, batch))

    return jax.tree_util.tree_map_with_path(one, cache)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# Ambient-mesh activation constraints (model-code-side annotations)
# ---------------------------------------------------------------------------
DP = "__data_axes__"  # sentinel: expands to whichever of (pod, data) exist


def constrain(x, *entries):
    """``with_sharding_constraint`` against the ambient mesh
    (``jax.sharding.set_mesh``).

    No-op when no mesh is active (single-device tests) or when an entry does
    not divide its dim.  Entries: axis name, tuple of names, the DP sentinel
    (the batch axes of the current strategy), or None.  Axes already consumed
    by an earlier entry are dropped (keeps 'fsdp' pins valid).  Model code
    can therefore annotate unconditionally.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    names = set(mesh.axis_names)
    strategy = get_strategy()
    dp_axes = tuple(
        a
        for a in (("pod", "data") if strategy == "2d" else ("pod", "data", "model"))
        if a in names
    )
    used: set[str] = set()
    spec: list = []
    for dim, e in zip(x.shape, entries):
        if e == DP:
            e = dp_axes if len(dp_axes) != 1 else dp_axes[0]
        if e is None:
            spec.append(None)
            continue
        axes = tuple(a for a in (e if isinstance(e, tuple) else (e,)) if a in names and a not in used)
        if not axes:
            spec.append(None)
            continue
        size = int(np.prod([mesh.shape[a] for a in axes]))
        if dim % size == 0:
            used.update(axes)
            spec.append(axes if len(axes) > 1 else axes[0])
        else:
            spec.append(None)
    spec += [None] * (len(x.shape) - len(spec))
    return jax.lax.with_sharding_constraint(x, P(*spec))


def opt_state_shardings(opt_state, params_shardings):
    """AdamW moments mirror the parameter shardings; step is replicated."""
    import dataclasses

    from repro.optim import AdamWState

    assert isinstance(opt_state, AdamWState) or hasattr(opt_state, "mu")
    mesh = jax.tree_util.tree_leaves(params_shardings)[0].mesh
    return AdamWState(
        step=NamedSharding(mesh, P()),
        mu=params_shardings,
        nu=params_shardings,
    )
