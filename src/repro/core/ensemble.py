"""Heterogeneous client-model ensembles (Eq. 1: average logits).

The paper's key aggregation move: average *logits*, never parameters —
which is what makes heterogeneous client architectures possible. Clients
are (CNNSpec, params) pairs.

Two evaluation paths:

  * ``ensemble_logits`` — reference implementation: a python loop over
    clients that unrolls under jit. Compile size and runtime scale O(m).
  * ``grouped_ensemble_logits`` — the fast path: clients are grouped by
    ``CNNSpec`` (``group_clients``), each group's params are stacked once
    at setup (``stack_grouped``) and the whole group is evaluated with a
    single ``jax.vmap`` forward — a 20-client homogeneous federation
    compiles/executes 1 batched forward instead of 20. Singleton groups
    fall back to a direct (un-vmapped) forward. The ``with_bn_stats``
    path needed by L_BN (Eq. 3) is supported: per-client stats are
    unstacked from the vmapped forward so ``losses.bn_loss`` is unchanged.

Grouping reorders clients by first occurrence of their spec; both the
logit average and L_BN are order-invariant sums over clients, so the two
paths agree to float tolerance (tests/test_fastpath.py).

With a ("clients", "data") mesh (``grouped_ensemble_logits(..., mesh=)``,
routed by ``scfg.ensemble_shard_mode`` — see fl/sharding.py) each stacked
group's leading client dim is sharded over the ``clients`` axis and the
group sum lowers to per-shard partial sums + one ``psum`` via
``shard_map`` — the host realization of the pod-axis all-reduce in
repro/core/dense_llm.py (DESIGN.md §8). Groups whose size the axis does
not divide keep the single-device vmap path, so the mesh is always
correctness-safe.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import numpy as np

from repro.models.cnn import (CNNSpec, cnn_apply, cnn_stack_apply_grouped,
                              is_groupable)


@dataclass
class Client:
    spec: CNNSpec
    params: dict
    n_data: int = 0                 # |D_k| (FedAvg weighting; DENSE ignores)
    class_counts: jnp.ndarray | None = None


def ensemble_logits(specs: Sequence[CNNSpec], params_list, x: jnp.ndarray,
                    *, with_bn_stats: bool = False):
    """Eq. (1): D(x) = (1/m) sum_k f^k(x). Eval-mode (running BN stats).

    Reference (unrolled) path. specs are static (shape info); params_list
    is a traced pytree so jitted callers don't bake client weights in as
    constants. with_bn_stats additionally returns each client's
    per-BN-layer batch statistics of x — the inputs to L_BN (Eq. 3).
    """
    logits_sum = None
    all_stats = []
    for spec, params in zip(specs, params_list):
        lg, _, stats = cnn_apply(params, spec, x, train=False)
        lg = lg.astype(jnp.float32)
        logits_sum = lg if logits_sum is None else logits_sum + lg
        if with_bn_stats:
            all_stats.append(stats)
    avg = logits_sum / len(specs)
    if with_bn_stats:
        return avg, all_stats
    return avg


def split_clients(clients: Sequence[Client]):
    """-> (static spec tuple, traced params list)."""
    return tuple(c.spec for c in clients), [c.params for c in clients]


def group_clients(clients: Sequence[Client]):
    """Group clients by architecture with a deterministic key order.

    -> list of (spec, client_indices) pairs, ordered by the *first
    occurrence* of each spec (insertion order — never a set, whose
    iteration order is unstable across processes).
    """
    groups: dict[CNNSpec, list[int]] = {}
    for i, c in enumerate(clients):
        groups.setdefault(c.spec, []).append(i)
    return [(spec, tuple(idx)) for spec, idx in groups.items()]


def _stack_chunked(trees, chunk: int | None = None):
    """Stack a list of per-client pytrees on a new leading axis.

    ``chunk > 0`` builds the stack in fixed-size slices concatenated on
    device (DESIGN.md §13): the host-side transfer buffer peaks at
    O(chunk) client trees instead of one O(m) staging blob, which is
    what lets a m=1000 federation stack without an m-sized host spike.
    Values are bitwise identical either way (stack/concatenate move
    bytes, they don't compute).
    """
    if chunk and 0 < chunk < len(trees):
        parts = [jax.tree.map(lambda *xs: jnp.stack(xs),
                              *trees[i:i + chunk])
                 for i in range(0, len(trees), chunk)]
        return jax.tree.map(lambda *ps: jnp.concatenate(ps, 0), *parts)
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def stack_grouped(clients: Sequence[Client], *, apply_masks: bool = True,
                  chunk: int | None = None):
    """Build the grouped-ensemble representation.

    -> (gspecs, gparams) where gspecs is the *static* part — a tuple of
    (CNNSpec, group_size) — and gparams the *traced* part: one params
    pytree per group, stacked along a leading client axis for groups of
    size > 1 and kept flat for singletons (which skip vmap entirely).
    Stack once at setup; jitted steps then take gparams as an argument so
    client weights are not baked in as constants.

    A federation built by the grouped client-training engine
    (fl/federation.ClientList) already IS this representation — its
    prebuilt (gspecs, gparams) is returned as-is, so params trained on
    the stacked client axis flow into the ensemble without an
    unstack/restack round trip through host memory.

    A federation that went through upload admission
    (fl.protocol.admit_uploads) carries ``group_masks``: with
    ``apply_masks=True`` (default) quarantined clients are statically
    sliced out here (``apply_group_masks``), so EVERY grouped consumer —
    the DENSE teacher, the baselines, the sharded psum path — sees
    exactly the representation a federation built without those clients
    would produce. ``apply_masks=False`` returns the raw full-width
    stack (quarantined slots zero-filled).
    """
    masks = getattr(clients, "group_masks", None) if apply_masks else None
    pre = getattr(clients, "grouped", None)
    if pre is not None:
        gspecs, gparams = pre
    else:
        gspecs, gparams = [], []
        for spec, idx in group_clients(clients):
            gspecs.append((spec, len(idx)))
            if len(idx) == 1:
                gparams.append(clients[idx[0]].params)
            else:
                # chunk > 0 stages the stack in O(chunk) host slices
                # (DESIGN.md §13); bitwise the same values either way
                gparams.append(_stack_chunked(
                    [clients[i].params for i in idx], chunk))
    if masks is not None and any(m is not None for m in masks):
        return apply_group_masks(gspecs, gparams, masks)
    return tuple(gspecs), gparams


def apply_group_masks(gspecs, gparams, group_masks):
    """Statically slice the survivors out of a grouped representation.

    ``group_masks`` is per-group: None (whole group survives) or a host
    numpy bool array over the group's client axis. Because the masks are
    static (admission decisions are made on host, before tracing), the
    surviving rows are gathered with constant indices and fully-
    quarantined groups disappear from the unrolled group loop — the
    result is the *same pytree values and the same downstream program* as
    a federation built without the quarantined clients, which is what
    makes quarantine bit-identical to removal (tests/test_faults.py).

    -> (gspecs, gparams) with surviving sizes; a group reduced to one
    client becomes a flat singleton (matching ``stack_grouped`` of the
    reduced federation).
    """
    if group_masks is None or all(m is None for m in group_masks):
        return tuple(gspecs), list(gparams)
    if len(group_masks) != len(gspecs):
        raise ValueError(f"group_masks has {len(group_masks)} entries for "
                         f"{len(gspecs)} groups")
    new_specs, new_params = [], []
    for (spec, size), params, gm in zip(gspecs, gparams, group_masks):
        if gm is None:
            new_specs.append((spec, size))
            new_params.append(params)
            continue
        gm = np.asarray(gm, bool)
        if gm.shape != (size,):
            raise ValueError(f"group mask shape {gm.shape} != ({size},)")
        idx = np.nonzero(gm)[0]
        if idx.size == 0:
            continue                     # fully quarantined: static skip
        if idx.size == size:
            new_specs.append((spec, size))
            new_params.append(params)
        elif idx.size == 1:
            new_specs.append((spec, 1))
            new_params.append(jax.tree.map(
                lambda a, _i=int(idx[0]): a[_i], params))
        else:
            new_specs.append((spec, int(idx.size)))
            new_params.append(jax.tree.map(lambda a: a[idx], params))
    if not new_specs:
        raise ValueError("every client is quarantined: empty ensemble")
    return tuple(new_specs), new_params


def _group_stack_forward(params, spec, x, size, with_stats):
    """(logits (size, B, K) f32, stacked stats) for one stacked group —
    fused grouped-channel forward for groupable kinds (the conv-stack
    zoo AND the ResNet/WRN kinds — models/cnn.py), vmap fallback for
    anything else."""
    if is_groupable(spec.kind):
        # fully-fused grouped-channel forward (models/cnn.py)
        lgs, stacked_stats = cnn_stack_apply_grouped(
            params, spec, x, size, with_stats=with_stats)
        return lgs.astype(jnp.float32), stacked_stats

    def one(p, _spec=spec):
        lg_k, _, st_k = cnn_apply(p, _spec, x, train=False)
        return lg_k.astype(jnp.float32), st_k

    return jax.vmap(one)(params)


def _chunked_stack_sum(params, spec, x, size, chunk, with_stats,
                       reduce=None):
    """Stream one stacked group's logit sum in ``chunk``-client slices
    (DESIGN.md §13): ``lax.scan`` over sub-stacks of the leading client
    axis, each chunk's (chunk, B, K) logits folded into an fp32 (B, K)
    accumulator — the teacher never materializes the full (size, B, K)
    activation block, and the scan body is rematerialized
    (``jax.checkpoint``) so differentiation (the generator's teacher
    gradient) re-runs chunks instead of keeping per-chunk residuals
    alive. ``reduce`` (e.g. a ``psum`` under shard_map) is applied to
    every chunk's partial sum, the remainder chunk included.

    Per-client BN stats are still returned with the full (size, ...)
    leading dim — they are (size, C)-small; the memory win is the
    activations, not the stats.
    """
    r = reduce if reduce is not None else (lambda s: s)
    nc, rem = divmod(size, chunk)
    acc = jnp.zeros((x.shape[0], spec.num_classes), jnp.float32)
    stats = None
    if nc:
        main = jax.tree.map(
            lambda a: a[:nc * chunk].reshape((nc, chunk) + a.shape[1:]),
            params)

        @jax.checkpoint
        def fwd(p_c):
            return _group_stack_forward(p_c, spec, x, chunk, with_stats)

        def body(carry, p_c):
            lgs, st = fwd(p_c)
            return carry + r(jnp.sum(lgs, axis=0)), st

        acc, st_main = jax.lax.scan(body, acc, main)
        stats = jax.tree.map(
            lambda a: a.reshape((nc * chunk,) + a.shape[2:]), st_main)
    if rem:
        tail = jax.tree.map(lambda a: a[nc * chunk:], params)
        lgs_t, st_t = _group_stack_forward(tail, spec, x, rem, with_stats)
        acc = acc + r(jnp.sum(lgs_t, axis=0))
        stats = st_t if stats is None else jax.tree.map(
            lambda a, b: jnp.concatenate([a, b], axis=0), stats, st_t)
    return acc, stats


def _group_sum_sharded(params, spec, x, size, mesh, with_stats,
                       chunk=None):
    """Sharded group sum: the leading client dim splits over the mesh's
    ``clients`` axis, each shard runs the same fused/vmapped forward on
    its size // axis clients, and the sum lowers to ONE ``psum`` — or,
    with ``chunk`` set, to one psum per scanned sub-chunk
    (``_chunked_stack_sum``), keeping the replicated fp32 accumulator
    exact while no shard ever materializes its full local logit block.
    The psum runs in named scope ``psum`` under the group's, so a trace
    charges the collective to the teacher. With stats, each shard's
    per-client BN statistics are gathered inside the shard_map by one
    tiled all-gather of the leaves packed side by side (scope
    ``all_gather``): slicing one client out of a client-sharded stats
    stack would cost a collective-permute per client and leaf (1,200 a
    generator step for 20 ResNet-18s on four devices), and gathering
    leaf by leaf one all-gather per leaf (102).

    Returns (group_sum (B, K) f32 replicated, stacked stats with the full
    (size, ...) leading dim, replicated). Callers guarantee divisibility
    (fl.sharding.group_shardable).
    """
    from repro.fl.sharding import CLIENT_AXIS, client_axis_size

    loc = size // client_axis_size(mesh)

    def psum(v):
        with jax.named_scope("psum"):
            return jax.lax.psum(v, CLIENT_AXIS)

    def gather(tree):
        leaves, treedef = jax.tree.flatten(tree)
        out = [None] * len(leaves)
        for dt in dict.fromkeys(a.dtype for a in leaves):
            idx = [i for i, a in enumerate(leaves) if a.dtype == dt]
            packed = jnp.concatenate(
                [leaves[i].reshape(loc, -1) for i in idx], axis=1)
            with jax.named_scope("all_gather"):
                full = jax.lax.all_gather(packed, CLIENT_AXIS, tiled=True)
            off = 0
            for i in idx:
                n = leaves[i].size // loc
                out[i] = full[:, off:off + n].reshape(
                    (size,) + leaves[i].shape[1:])
                off += n
        return jax.tree.unflatten(treedef, out)

    def local(p_shard, xb):
        if chunk and 0 < chunk < loc:
            s, st = _chunked_stack_sum(
                p_shard, spec, xb, loc, chunk, with_stats, reduce=psum)
        else:
            lgs, st = _group_stack_forward(p_shard, spec, xb, loc,
                                           with_stats)
            s = psum(jnp.sum(lgs, axis=0))
        return (s, gather(st)) if with_stats else s

    out_specs = (P(), P()) if with_stats else P()
    out = jax.shard_map(local, mesh=mesh, in_specs=(P(CLIENT_AXIS), P()),
                        out_specs=out_specs, check_vma=False)(params, x)
    return out if with_stats else (out, [])


def grouped_ensemble_logits(gspecs, gparams, x: jnp.ndarray, *,
                            with_bn_stats: bool = False, mesh=None,
                            group_masks=None, chunk: int | None = None):
    """Eq. (1) over the grouped representation — one vmapped forward per
    architecture group instead of one unrolled forward per client.

    Matches ``ensemble_logits`` up to float tolerance; with_bn_stats
    returns a flat per-client stats list (group order) compatible with
    ``losses.bn_loss``, which is order-invariant.

    mesh: optional ("clients", "data") mesh (fl/sharding.py). Stacked
    groups whose size the ``clients`` axis divides evaluate as one
    shard_map whose group sum is a single psum over that axis; other
    groups (and singletons) keep the single-device path. Each stacked
    group's clients are counted, at trace time, as sharded or
    replicated (``fl.sharding.shards_group``).

    group_masks: optional per-group survivor masks (fl.protocol
    admission). Statically sliced out up front (``apply_group_masks``),
    so the average runs over survivors only — divisor included — and the
    sharded path sees the surviving group size (re-checking
    divisibility, falling back to the single-device forward when the
    reduced size no longer shards).

    chunk: > 0 streams each stacked group's logit sum through
    ``chunk``-client scanned slices (``_chunked_stack_sum``, DESIGN.md
    §13) so the stage-2 teacher never materializes a (size, B, K)
    activation block; routed from ``scfg.teacher_chunk``
    (configs.backend.resolve_exec_policy). Sum order within a group is
    unchanged — partial fp32 sums accumulate in client order — so the
    result matches the unchunked path to float tolerance (and bitwise
    when the chunk divides the group evenly on one device).
    """
    if group_masks is not None:
        gspecs, gparams = apply_group_masks(gspecs, gparams, group_masks)
    if mesh is not None:
        from repro.fl.sharding import shards_group
    m = sum(size for _, size in gspecs)
    logits_sum = None
    all_stats = []
    for (spec, size), params in zip(gspecs, gparams):
        # one scope per group: a trace splits the ensemble's device time
        # by architecture
        with jax.named_scope(spec.kind):
            if size == 1:
                lg, _, stats = cnn_apply(params, spec, x, train=False)
                group_sum = lg.astype(jnp.float32)
                if with_bn_stats:
                    all_stats.append(stats)
            else:
                if mesh is not None and shards_group(mesh, size,
                                                     spec.kind):
                    group_sum, stacked_stats = _group_sum_sharded(
                        params, spec, x, size, mesh, with_bn_stats,
                        chunk=chunk)
                elif chunk and 0 < chunk < size:
                    group_sum, stacked_stats = _chunked_stack_sum(
                        params, spec, x, size, chunk, with_bn_stats)
                else:
                    lgs, stacked_stats = _group_stack_forward(
                        params, spec, x, size, with_bn_stats)
                    group_sum = jnp.sum(lgs, axis=0)
                if with_bn_stats:
                    for k in range(size):
                        all_stats.append(jax.tree.map(lambda a, _k=k: a[_k],
                                                      stacked_stats))
        logits_sum = group_sum if logits_sum is None \
            else logits_sum + group_sum
    avg = logits_sum / m
    if with_bn_stats:
        return avg, all_stats
    return avg


def stack_homogeneous(clients: Sequence[Client]):
    """Stack same-architecture client params for a vmapped ensemble."""
    groups = group_clients(clients)
    assert len(groups) == 1, "stack_homogeneous requires identical specs"
    spec, idx = groups[0]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                           *[clients[i].params for i in idx])
    return spec, stacked


def ensemble_logits_stacked(spec: CNNSpec, stacked: dict, x: jnp.ndarray):
    """Vmapped homogeneous ensemble — one batched forward instead of m."""
    def one(p):
        return cnn_apply(p, spec, x, train=False)[0].astype(jnp.float32)
    return jnp.mean(jax.vmap(one)(stacked), axis=0)
