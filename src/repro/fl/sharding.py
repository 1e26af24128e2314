"""Client-axis mesh sharding: one SPMD vocabulary for every stacked-client
computation, from grouped local training to the ensemble teacher.

The grouped engine (fl/federation.py) and the grouped ensemble
(core/ensemble.stack_grouped) both hold a federation as per-architecture
pytrees stacked along a leading client dim of size m. This module is the
single place that maps that dim onto a mesh:

  * ``launch.mesh.make_client_mesh`` builds the ("clients", "data") mesh;
    ``resolve_mesh(scfg)`` routes it from ``scfg.ensemble_shard_mode``
    ("none" -> single-device, "clients" -> shard the client axis).
  * ``client_stack_sharding`` / ``put_stacked`` place an (m, ...) stack
    with the leading dim split over ``clients`` — used for param and
    momentum carries AND for the (m, steps, batch) batch-plan tensors, so
    grouped local training is SPMD by placement alone (GSPMD propagates
    the client axis through the vmapped step; per-client math never
    crosses shards).
  * ``stack_specs`` prepends a stacked-client axis to an existing
    PartitionSpec tree — the shared vocabulary between this host path and
    ``core.dense_llm``'s pod-sharded cell, whose ensemble dim is the same
    leading client dim under the name "pod".
  * ``core.ensemble.grouped_ensemble_logits(..., mesh=...)`` lowers the
    logit mean to per-shard partial sums + ONE ``psum`` over ``clients``
    via ``shard_map`` (DESIGN.md §8).

A group only shards when its size is divisible by the ``clients`` axis
(``group_shardable``); otherwise it is placed replicated and the existing
single-device vmap path runs unchanged — ``ensemble_shard_mode="clients"``
is therefore always correctness-safe, on any device count. The teacher
records which way each stacked group went (``shards_group``): counters
``ensemble.sharded_clients`` / ``ensemble.replicated_clients`` and, for a
group that falls back, one warning per group shape. Equivalence is
exercised on CPU via ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(tests/test_client_sharding.py, CI job ``sharding-equivalence``).
"""
from __future__ import annotations

import warnings

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.configs.backend import SHARD_MODES, resolve_exec_policy
from repro.launch.mesh import make_client_mesh

CLIENT_AXIS = "clients"
# (kind, group size, clients axis) of the fallbacks already warned about
_WARNED: set = set()


def resolve_mesh(scfg):
    """Mesh routing for the CNN-scale host path: None (single-device)
    or the ("clients", "data") host mesh. ``scfg`` may be a config, an
    already-resolved ExecPolicy, or None — the shard mode comes from the
    backend execution-policy registry (configs/backend.py, DESIGN.md
    §11; "none" on every backend unless ``scfg.ensemble_shard_mode``
    opts in)."""
    mode = resolve_exec_policy(scfg).ensemble_shard
    if mode == "none":
        return None
    return make_client_mesh()


def client_axis_size(mesh) -> int:
    if mesh is None:
        return 1
    return int(dict(mesh.shape).get(CLIENT_AXIS, 1))


def group_shardable(mesh, size: int) -> bool:
    """A stacked group shards iff the clients axis divides its size (each
    shard then carries size // axis whole clients)."""
    return mesh is not None and size > 1 \
        and size % client_axis_size(mesh) == 0


def shards_group(mesh, size: int, kind: str) -> bool:
    """``group_shardable``, recorded for a stacked group of the teacher
    (trace time): its clients are added to ``ensemble.sharded_clients``
    or, where the mesh leaves the group replicated, to
    ``ensemble.replicated_clients``, and the first fallback of each
    group shape warns, since every device then runs the whole group."""
    ok = group_shardable(mesh, size)
    if mesh is None or size < 2:
        return ok
    obs.count("ensemble.sharded_clients" if ok
              else "ensemble.replicated_clients", size)
    shape = (kind, size, client_axis_size(mesh))
    if not ok and shape not in _WARNED:
        _WARNED.add(shape)
        warnings.warn(
            f"ensemble_shard_mode='clients': the {shape[2]}-way "
            f"{CLIENT_AXIS!r} axis does not divide the {size}-client {kind} "
            "group, which runs replicated on every device", RuntimeWarning,
            stacklevel=2)
    return ok


def stack_specs(inner_specs, axis):
    """Prepend a stacked-client axis to an existing PartitionSpec tree.

    The shared spec vocabulary between the host and pod paths: the host
    CNN stacks use axis="clients" over replicated inner specs; the LLM
    pod cell (core.dense_llm.pod_stack_specs) prepends axis="pod" to its
    Megatron param specs. axis=None yields a replicated leading dim.
    """
    return jax.tree.map(lambda s: P(axis, *s), inner_specs,
                        is_leaf=lambda x: isinstance(x, P))


def client_stack_sharding(mesh) -> NamedSharding:
    """Leading client dim over ``clients``; everything else replicated."""
    return NamedSharding(mesh, P(CLIENT_AXIS))


def replicated_sharding(mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def put_stacked(tree, mesh, size: int):
    """Place a leading-client-axis stacked pytree on the mesh: sharded
    over ``clients`` when the group size divides, else replicated."""
    if mesh is None:
        return tree
    sh = client_stack_sharding(mesh) if group_shardable(mesh, size) \
        else replicated_sharding(mesh)
    return jax.device_put(tree, sh)


def put_replicated(tree, mesh):
    if mesh is None:
        return tree
    return jax.device_put(tree, replicated_sharding(mesh))


def put_grouped(gspecs, gparams, mesh):
    """Place a grouped-ensemble representation (ensemble.stack_grouped):
    each stacked group client-sharded when divisible, singletons and
    ragged groups replicated."""
    if mesh is None:
        return gparams
    return [put_replicated(params, mesh) if size == 1
            else put_stacked(params, mesh, size)
            for (_, size), params in zip(gspecs, gparams)]


__all__ = ["CLIENT_AXIS", "SHARD_MODES", "resolve_mesh", "client_axis_size",
           "group_shardable", "shards_group", "stack_specs",
           "client_stack_sharding",
           "replicated_sharding", "put_stacked", "put_replicated",
           "put_grouped"]
