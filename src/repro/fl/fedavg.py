"""FedAvg aggregation [44] — the paper's primary baseline (homogeneous
models only; Table 2 omits it for heterogeneous federations).

The aggregation itself is ONE jitted weighted tree-reduce over the
stacked client axis (``fedavg_stacked``). ``fedavg`` keeps the
list-of-clients API: when the federation was built by the grouped engine
(fl/federation.ClientList) the already-stacked group params are reduced
directly; otherwise the client trees are stacked once here.

Two reduction topologies (``mode``, routed from
``scfg.fedavg_mode`` through the execution-policy registry —
configs/backend.py, DESIGN.md §13):

  * ``"flat"`` (default) — one weighted sum over the full client axis.
  * ``"tree"`` — hierarchical: clients reduce in fan-in-``branch``
    groups per level, each node carrying its subtree's weighted mean and
    total n_data so every level reweights exactly (node = Σ wᵢvᵢ / Σ wᵢ
    in fp32, node weight = Σ wᵢ — the same invariant real FL
    aggregation servers keep when edge aggregators pre-combine uploads).
    The root equals the flat sum up to fp32 summation-order noise
    (tests/test_scale.py); with a ("clients", "data") mesh each shard
    tree-reduces its local clients and the cross-shard combine is a
    weighted psum pair over the ``clients`` axis.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ensemble import Client


def _check_n_data(n_data) -> np.ndarray:
    n = np.asarray(n_data, np.float64)
    if n.size == 0:
        raise ValueError("FedAvg weights are n_k / n; got an empty "
                         "n_data list")
    if np.any(n <= 0):
        # only the offending entries: interpolating all m counts is
        # unreadable at the ROADMAP's m=1000 target
        bad = [(i, v) for i, v in enumerate(np.asarray(n_data).tolist())
               if v <= 0]
        shown, extra = bad[:5], len(bad) - 5
        raise ValueError(
            "FedAvg weights are n_k / n; every client must report "
            f"n_data > 0, got (client, n_data): {shown}"
            + (f" ... and {extra} more" if extra > 0 else ""))
    return n


@jax.jit
def _weighted_reduce(stacked, w):
    """theta_S = sum_k w_k theta^k over the leading (client) axis."""
    def avg(leaf):
        wf = w.astype(jnp.float32).reshape((-1,) + (1,) * (leaf.ndim - 1))
        return jnp.sum(wf * leaf.astype(jnp.float32), 0).astype(leaf.dtype)

    return jax.tree.map(avg, stacked)


def _tree_level(v, w, branch: int):
    """One reduction level: (m, ...) values + (m,) weights -> ceil(m/b)
    weighted-mean nodes + their summed weights. The tail group is padded
    with zero-weight children; it always keeps >= 1 real child (pad <
    branch), so no node divides by zero (weights are positive —
    _check_n_data)."""
    m = v.shape[0]
    pad = (-m) % branch
    if pad:
        v = jnp.concatenate(
            [v, jnp.zeros((pad,) + v.shape[1:], v.dtype)], 0)
        w = jnp.concatenate([w, jnp.zeros((pad,), w.dtype)], 0)
    g = v.shape[0] // branch
    vg = v.reshape((g, branch) + v.shape[1:])
    wg = w.reshape(g, branch)
    wsum = jnp.sum(wg, 1)
    wf = wg.reshape((g, branch) + (1,) * (v.ndim - 1))
    node = jnp.sum(vg * wf, 1) / wsum.reshape((g,) + (1,) * (v.ndim - 1))
    return node, wsum


def _tree_reduce_leaf(leaf, w, branch: int):
    """Full trace-time tree reduce of one (m, ...) leaf to its root
    weighted mean — static level loop, fp32 accumulation throughout."""
    v, ww = leaf.astype(jnp.float32), w.astype(jnp.float32)
    while v.shape[0] > 1:
        v, ww = _tree_level(v, ww, branch)
    return v[0].astype(leaf.dtype)


@functools.partial(jax.jit, static_argnames=("branch",))
def _tree_reduce(stacked, w, branch: int):
    return jax.tree.map(lambda a: _tree_reduce_leaf(a, w, branch), stacked)


def _tree_reduce_sharded(stacked, w, branch: int, mesh):
    """Tree reduce with the client axis sharded over ``clients``: each
    shard tree-reduces its local clients to one (value, weight) node,
    then the cross-shard combine is a weighted psum pair — the mesh is
    the top level of the tree. Callers guarantee divisibility
    (fl.sharding.group_shardable)."""
    from jax.sharding import PartitionSpec as P

    from repro.fl.sharding import CLIENT_AXIS

    def local(st, wl):
        def one(leaf):
            v, ww = leaf.astype(jnp.float32), wl.astype(jnp.float32)
            while v.shape[0] > 1:
                v, ww = _tree_level(v, ww, branch)
            num = jax.lax.psum(v[0] * ww[0], CLIENT_AXIS)
            den = jax.lax.psum(ww[0], CLIENT_AXIS)
            return (num / den).astype(leaf.dtype)
        return jax.tree.map(one, st)

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(CLIENT_AXIS), P(CLIENT_AXIS)),
                         out_specs=P(), check_vma=False)(stacked, w)


def fedavg_stacked(stacked_params, n_data, survivor_mask=None, *,
                   mode: str = "flat", branch: int = 8,
                   mesh=None) -> dict:
    """FedAvg over params stacked on a leading client axis — the grouped
    engine's native representation. n_data: per-client example counts
    (must be positive; they define the weights n_k / n).

    survivor_mask: optional STATIC host bool mask over the client axis
    (fl.protocol admission). Survivors are sliced out with constant
    indices before the reduce — same rows, same weights, same program as
    a federation stacked without the quarantined clients, so masked
    FedAvg is bit-identical to FedAvg over the survivors
    (tests/test_faults.py). Quarantined clients' n_data never enters the
    weight normalization (and is exempt from the positivity check).

    mode="tree" reduces hierarchically with fan-in ``branch`` per level
    (module docstring); with a ("clients", "data") ``mesh`` whose axis
    divides the (surviving) client count, each shard tree-reduces
    locally and the root combine is a weighted psum pair."""
    if survivor_mask is not None:
        mask = np.asarray(survivor_mask, bool)
        n_all = np.asarray(n_data)
        if mask.shape != (n_all.shape[0],):
            raise ValueError(f"survivor_mask shape {mask.shape} != "
                             f"({n_all.shape[0]},)")
        if not mask.any():
            raise ValueError("FedAvg over zero surviving clients")
        idx = np.nonzero(mask)[0]
        n_data = n_all[idx]
        if not mask.all():
            stacked_params = jax.tree.map(lambda a: a[idx], stacked_params)
    n = _check_n_data(n_data)
    w = jnp.asarray(n / n.sum())
    if mode == "tree":
        from repro.fl.sharding import group_shardable
        if group_shardable(mesh, int(w.shape[0])):
            return _tree_reduce_sharded(stacked_params, w, int(branch),
                                        mesh)
        return _tree_reduce(stacked_params, w, int(branch))
    if mode != "flat":
        raise ValueError(f"unknown fedavg mode {mode!r} "
                         "(expected 'flat' or 'tree')")
    return _weighted_reduce(stacked_params, w)


def fedavg(clients: Sequence[Client], *, policy=None, mesh=None) -> dict:
    """theta_S = sum_k (n_k / n) theta^k.

    A federation that went through upload admission carries
    ``survivor_mask``; quarantined clients are excluded from the average
    (bit-identically to a federation without them).

    policy: an ExecPolicy (configs.backend.resolve_exec_policy) routing
    the reduction topology — ``fedavg``/``fedavg_branch`` (DESIGN.md
    §13). Default is today's flat weighted sum."""
    mode = policy.fedavg if policy is not None else "flat"
    branch = policy.fedavg_branch if policy is not None else 8
    kinds = {c.spec for c in clients}
    if len(kinds) != 1:
        raise ValueError("FedAvg requires homogeneous client models; got "
                         f"{[c.spec.kind for c in clients]}")
    mask = getattr(clients, "survivor_mask", None)
    n_data = [c.n_data for c in clients]
    grouped = getattr(clients, "grouped", None)
    if grouped is not None and len(grouped[0]) == 1 \
            and grouped[0][0][1] == len(clients) and len(clients) > 1:
        # grouped-engine federation: reduce the stacked axis directly
        return fedavg_stacked(grouped[1][0], n_data, survivor_mask=mask,
                              mode=mode, branch=branch, mesh=mesh)
    if mask is not None:
        mask = np.asarray(mask, bool)
        if not mask.any():
            raise ValueError("FedAvg over zero surviving clients")
        clients = [c for c, ok in zip(clients, mask) if ok]
        n_data = [c.n_data for c in clients]
    _check_n_data(n_data)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                           *[c.params for c in clients])
    return fedavg_stacked(stacked, n_data, mode=mode, branch=branch,
                          mesh=mesh)
