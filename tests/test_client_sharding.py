"""Client-axis mesh sharding (fl/sharding.py) + fedavg_stacked edges.

The sharded paths are placement/lowering choices, never math changes:
``ensemble_shard_mode="clients"`` must reproduce the single-device
grouped teacher logits and grouped local-update params to float
tolerance for the same seeds. These tests run at ANY device count — on
the plain tier-1 host the ("clients", "data") mesh is degenerate
(axis size 1) and they pin the routing; CI's ``sharding-equivalence``
job reruns them under XLA_FLAGS=--xla_force_host_platform_device_count=8
where the client axis genuinely splits across 8 devices (conftest.py
forbids forcing the device count in-process, so the multi-device regime
lives in the CI env, not here).
"""
import dataclasses
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.configs.paper_cifar import DenseExperimentConfig
from repro.core import losses as LS
from repro.core.ensemble import (Client, ensemble_logits,
                                 grouped_ensemble_logits, split_clients,
                                 stack_grouped)
from repro.data.pipeline import build_batch_plan, pad_shards
from repro.fl import sharding as FS
from repro.fl.client import local_update_grouped
from repro.fl.fedavg import fedavg_stacked
from repro.launch.mesh import make_client_mesh
from repro.models.cnn import CNNSpec, cnn_init


def _tree_max_diff(a, b):
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ------------------------------------------------------ mesh + spec unit ---

def test_make_client_mesh_axes():
    mesh = make_client_mesh()
    assert mesh.axis_names == ("clients", "data")
    n = len(jax.devices())
    assert dict(mesh.shape) == {"clients": n, "data": 1}
    mesh2 = make_client_mesh(data=n)        # all devices on the data axis
    assert dict(mesh2.shape) == {"clients": 1, "data": n}


def test_resolve_mesh_routing():
    assert FS.resolve_mesh(SimpleNamespace(ensemble_shard_mode="none")) is None
    assert FS.resolve_mesh(SimpleNamespace()) is None      # attr missing
    mesh = FS.resolve_mesh(SimpleNamespace(ensemble_shard_mode="clients"))
    assert mesh is not None and "clients" in mesh.axis_names
    with pytest.raises(ValueError):
        FS.resolve_mesh(SimpleNamespace(ensemble_shard_mode="pods"))


def test_group_shardable_divisibility():
    mesh8 = SimpleNamespace(shape={"clients": 8, "data": 1})
    assert FS.client_axis_size(mesh8) == 8
    assert FS.client_axis_size(None) == 1
    assert FS.group_shardable(mesh8, 8)
    assert FS.group_shardable(mesh8, 16)
    assert not FS.group_shardable(mesh8, 3)   # 3 % 8 != 0 -> replicate
    assert not FS.group_shardable(mesh8, 1)   # singletons never shard
    assert not FS.group_shardable(None, 8)


def test_stack_specs_shared_vocabulary():
    """The host 'clients' path and the LLM 'pod' path prepend the same
    leading client dim through one helper (fl.sharding.stack_specs)."""
    from repro.core.dense_llm import pod_stack_specs
    inner = {"w": P(None, "model"), "b": P()}
    got = FS.stack_specs(inner, "clients")
    assert got == {"w": P("clients", None, "model"), "b": P("clients")}
    pod_mesh = SimpleNamespace(axis_names=("pod", "data", "model"))
    host_mesh = SimpleNamespace(axis_names=("data", "model"))
    assert pod_stack_specs(inner, pod_mesh)["w"] == P("pod", None, "model")
    assert pod_stack_specs(inner, host_mesh)["w"] == P(None, None, "model")


# ------------------------------------------------- fedavg_stacked edges ---

def test_fedavg_stacked_single_client_group():
    sp = CNNSpec(kind="cnn1", num_classes=4, in_ch=1, width=0.25,
                 image_size=8)
    params = cnn_init(jax.random.PRNGKey(0), sp)
    stacked = jax.tree.map(lambda a: a[None], params)   # m=1 leading axis
    out = fedavg_stacked(stacked, [17])
    assert _tree_max_diff(out, params) == 0.0


def test_fedavg_stacked_zero_weight_rejection():
    stacked = {"w": jnp.ones((3, 2))}
    for bad in ([4, 0, 2], [4, -1, 2], []):
        with pytest.raises(ValueError):
            fedavg_stacked(stacked, bad)


def test_fedavg_stacked_dtype_preservation():
    stacked = {"w": jnp.ones((4, 8), jnp.bfloat16),
               "b": jnp.arange(4 * 3, dtype=jnp.float32).reshape(4, 3)}
    out = fedavg_stacked(stacked, [1, 1, 1, 1])
    assert out["w"].dtype == jnp.bfloat16
    assert out["b"].dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out["b"]),
                               np.arange(12, dtype=np.float32)
                               .reshape(4, 3).mean(0), atol=1e-5)


def test_fedavg_stacked_on_client_sharded_params():
    """The stacked tree-reduce must accept client-sharded inputs (the
    grouped engine's output under ensemble_shard_mode='clients')."""
    mesh = make_client_mesh()
    m = 8
    stacked = {"w": jnp.arange(m * 4, dtype=jnp.float32).reshape(m, 4)}
    ref = fedavg_stacked(stacked, [2] * m)
    got = fedavg_stacked(FS.put_stacked(stacked, mesh, m), [2] * m)
    np.testing.assert_allclose(np.asarray(got["w"]), np.asarray(ref["w"]),
                               atol=1e-6)


# ------------------------------------- sharded-vs-unsharded equivalence ---

def _mk_clients(kinds, seed0=0, num_classes=6):
    out = []
    for i, k in enumerate(kinds):
        sp = CNNSpec(kind=k, num_classes=num_classes, in_ch=3, width=0.25,
                     image_size=8)
        out.append(Client(spec=sp,
                          params=cnn_init(jax.random.PRNGKey(seed0 + i), sp)))
    return out


@pytest.mark.parametrize("kinds", [("cnn1",) * 8,
                                   ("cnn1",) * 8 + ("cnn2",) * 8],
                         ids=["homog8", "hetero8+8"])
def test_sharded_ensemble_matches_unsharded(kinds):
    mesh = make_client_mesh()
    clients = _mk_clients(kinds)
    x = jax.random.normal(jax.random.PRNGKey(42), (8, 8, 8, 3))
    gspecs, gparams = stack_grouped(clients)
    ref, ref_stats = grouped_ensemble_logits(gspecs, gparams, x,
                                             with_bn_stats=True)
    gp_sh = FS.put_grouped(gspecs, gparams, mesh)
    got, got_stats = jax.jit(
        lambda gp, xb: grouped_ensemble_logits(gspecs, gp, xb,
                                               with_bn_stats=True,
                                               mesh=mesh))(gp_sh, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)
    assert len(got_stats) == len(kinds)
    np.testing.assert_allclose(float(LS.bn_loss(got_stats)),
                               float(LS.bn_loss(ref_stats)), rtol=1e-4)
    # and against the unrolled reference too
    specs, cparams = split_clients(clients)
    unrolled = ensemble_logits(specs, cparams, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(unrolled),
                               atol=1e-5)


@pytest.mark.parametrize("chunk", [None, 2])
def test_sharded_residual_group_matches_unrolled(chunk):
    """A stacked residual group under the client-sharded teacher: each
    shard runs models.cnn._grouped_resnet_map (cnn_apply unrolled over
    its clients, ``ensemble.grouped_unrolled``) with its BN stats
    gathered over ``clients``. At B=32, logits, L_BN
    and the generator's input gradient match the unrolled ensemble.
    Eight clients, so the group splits on 1, 2, 4 or 8 devices; with
    chunk=2 every shard holding more than two clients streams them."""
    mesh = make_client_mesh()
    clients = _mk_clients(("wrn16_1",) * 8)
    assert FS.group_shardable(mesh, len(clients))
    x = jax.random.normal(jax.random.PRNGKey(9), (32, 8, 8, 3))
    y = jnp.arange(x.shape[0]) % 6
    specs, cparams = split_clients(clients)
    gspecs, gparams = stack_grouped(clients)
    gp_sh = FS.put_grouped(gspecs, gparams, mesh)

    def sharded(xb):
        return grouped_ensemble_logits(gspecs, gp_sh, xb, with_bn_stats=True,
                                       mesh=mesh, chunk=chunk)

    def unrolled(xb):
        return ensemble_logits(specs, cparams, xb, with_bn_stats=True)

    def loss(out):
        avg, stats = out
        return LS.ce_loss(avg, y) + LS.bn_loss(stats)

    obs.reset()
    got, got_stats = jax.jit(sharded)(x)
    regimes = obs.snapshot()["counters"]
    assert regimes.get("ensemble.grouped_unrolled", 0) >= 1
    assert "ensemble.grouped_native" not in regimes
    obs.reset()
    ref, ref_stats = unrolled(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)
    assert len(got_stats) == len(clients)
    np.testing.assert_allclose(float(LS.bn_loss(got_stats)),
                               float(LS.bn_loss(ref_stats)), rtol=1e-4)
    g_got = jax.jit(jax.grad(lambda xb: loss(sharded(xb))))(x)
    g_ref = jax.grad(lambda xb: loss(unrolled(xb)))(x)
    scale = float(jnp.max(jnp.abs(g_ref)))
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref),
                               atol=1e-3 * scale)


def test_sharded_ensemble_nondivisible_group_falls_back():
    """A mesh whose clients axis does not divide the group size must give
    the unsharded answer (vmap fallback), not fail."""
    mesh = make_client_mesh()
    clients = _mk_clients(("cnn1",) * 3)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 8, 3))
    gspecs, gparams = stack_grouped(clients)
    ref = grouped_ensemble_logits(gspecs, gparams, x)
    gp_sh = FS.put_grouped(gspecs, gparams, mesh)
    got = grouped_ensemble_logits(gspecs, gp_sh, x, mesh=mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


def test_sharded_local_update_matches_unsharded():
    mesh = make_client_mesh()
    m, batch, epochs = 8, 8, 2
    rng = np.random.default_rng(0)
    spec = CNNSpec(kind="cnn1", num_classes=6, in_ch=3, width=0.25,
                   image_size=8)
    # ragged shards: masking + padding steps must survive sharding
    shards = [(rng.standard_normal((18 + 3 * k, 8, 8, 3))
               .astype(np.float32), rng.integers(0, 6, 18 + 3 * k))
              for k in range(m)]
    inits = [cnn_init(jax.random.PRNGKey(i), spec) for i in range(m)]
    stacked0 = jax.tree.map(lambda *a: jnp.stack(a), *inits)
    xs, ys = pad_shards(shards)
    plan = build_batch_plan([len(y) for _, y in shards], batch,
                            epochs=epochs, seeds=list(range(m)))
    ref, _ = local_update_grouped(jax.tree.map(jnp.copy, stacked0), spec,
                                  xs, ys, plan, num_classes=6)
    got, _ = local_update_grouped(jax.tree.map(jnp.copy, stacked0), spec,
                                  xs, ys, plan, num_classes=6, mesh=mesh)
    assert _tree_max_diff(got, ref) < 1e-6


SCFG = DenseExperimentConfig(
    n_clients=8, alpha=0.5, local_epochs=2, batch_size=16, num_classes=4,
    image_size=8, in_ch=3, train_per_class=24, test_per_class=8,
    client_kinds=("cnn1",) * 8, global_kind="cnn1", width=0.25, nz=16,
    t_g=2, epochs=3, synth_batch=16)


@pytest.mark.parametrize("kinds", [("cnn1",), ("cnn1", "cnn2")],
                         ids=["homog", "hetero2"])
def test_federation_shard_mode_equivalence(kinds):
    """ensemble_shard_mode='clients' end-to-end: same Dirichlet
    partition, same seeds -> identical trained client params (grouped
    local phase is placement-only SPMD). hetero2 cycles two kinds over 16
    clients -> two stacked groups of 8, both sharded on the 8-device CI
    mesh."""
    from repro.data import make_classification_data
    from repro.fl.protocol import build_federation
    scfg = dataclasses.replace(SCFG, n_clients=8 * len(kinds),
                               client_kinds=kinds * 8)
    data = make_classification_data(0, num_classes=scfg.num_classes,
                                    size=scfg.image_size, ch=scfg.in_ch,
                                    train_per_class=scfg.train_per_class,
                                    test_per_class=scfg.test_per_class)
    built = {}
    for mode in ("none", "clients"):
        s = dataclasses.replace(scfg, ensemble_shard_mode=mode)
        built[mode], _ = build_federation(jax.random.PRNGKey(0), s, data,
                                          seed=0)
    for ca, cb in zip(built["none"], built["clients"]):
        assert ca.spec == cb.spec
        assert _tree_max_diff(ca.params, cb.params) < 1e-6


def test_dense_server_shard_mode_equivalence():
    """The teacher under ensemble_shard_mode='clients' (psum-lowered
    logit mean) must train the same student as the single-device grouped
    path for the same key stream."""
    from repro.core import train_dense_server
    clients = _mk_clients(("cnn1",) * 8, num_classes=SCFG.num_classes)
    outs = {}
    for mode in ("none", "clients"):
        s = dataclasses.replace(SCFG, ensemble_shard_mode=mode)
        stu, _, hist = train_dense_server(jax.random.PRNGKey(3), clients, s)
        outs[mode] = (stu, hist)
    assert _tree_max_diff(outs["none"][0], outs["clients"][0]) < 5e-5
    np.testing.assert_allclose(outs["none"][1].gen_loss,
                               outs["clients"][1].gen_loss,
                               rtol=1e-3, atol=1e-5)


@pytest.mark.parametrize("kl", ["ref", "fused"])
def test_dense_fused_chunk_on_client_mesh_matches_unsharded(kl):
    """train_dense_server's fused chunk driver (one scanned program of
    loop_chunk epochs, donated carries) on the ("clients", "data") mesh
    against the same call without a mesh: eight clients of one residual
    kind, so the group shards on 1, 2, 4 or 8 devices. Same math, not
    the same bits (DESIGN.md §8): the first chunk's student within 5e-5
    and its losses within 1e-3. With the Pallas distill_kl pair (here in
    interpret mode) each device runs it whole on replicated logits. Every
    carry comes back replicated on the mesh, and the teacher counts all
    eight clients as sharded."""
    from repro import obs
    from repro.core import train_dense_server
    clients = _mk_clients(("wrn16_1",) * 8, num_classes=SCFG.num_classes)
    outs = {}
    for mode in ("none", "clients"):
        s = dataclasses.replace(SCFG, ensemble_shard_mode=mode,
                                loop_mode="fused", loop_chunk=2, epochs=2,
                                distill_kl_mode=kl)
        obs.reset()
        stu, gen, hist = train_dense_server(jax.random.PRNGKey(5), clients,
                                            s)
        outs[mode] = (stu, hist, obs.snapshot())
    (stu0, h0, snap0), (stu1, h1, snap1) = outs["none"], outs["clients"]
    assert _tree_max_diff(stu0, stu1) < 5e-5
    np.testing.assert_allclose(h0.gen_loss, h1.gen_loss, rtol=1e-3,
                               atol=1e-5)
    np.testing.assert_allclose(h0.dis_loss, h1.dis_loss, rtol=1e-3,
                               atol=1e-5)
    n = len(jax.devices())
    for leaf in jax.tree.leaves(stu1):
        assert leaf.sharding.is_fully_replicated
        assert len(leaf.sharding.device_set) == n
    assert snap1["counters"]["ensemble.sharded_clients"] > 0
    assert "ensemble.replicated_clients" not in snap1["counters"]
    assert "ensemble.sharded_clients" not in snap0["counters"]
    setup = {s["name"]: s["attrs"] for s in snap1["spans"]}["dense.setup"]
    assert setup == {"mesh": f"clients={n},data=1"}
