"""Federation-scale invariants (DESIGN.md §13): bucketed batch plans,
chunked group setup, hierarchical fedavg, chunked ensemble teacher.

The m=1000 scaling layers are all pure execution-shape knobs — every
test here pins an equivalence: bucketing/chunking never change a
client's trained params (bitwise), the tree reduce matches the flat
weighted sum to fp32 tolerance, the chunked teacher matches the
one-shot stacked forward, and survivor masks compose with buckets
unchanged. Plus the one inequality the knobs exist for: padded-step
waste under Dirichlet-like skew drops >= 3x with bucketing on.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.backend import resolve_exec_policy
from repro.configs.paper_cifar import DenseExperimentConfig
from repro.core.ensemble import (ensemble_logits, grouped_ensemble_logits,
                                 stack_grouped)
from repro.data.pipeline import (batches, bucket_members, build_batch_plan,
                                 plan_step_waste)
from repro.fl import admit_uploads, fedavg_stacked, train_clients_grouped
from repro.fl.client import local_update_bucketed
from repro.models.cnn import CNNSpec, cnn_init

SPEC = CNNSpec(kind="cnn1", num_classes=4, in_ch=1, width=0.25,
               image_size=8)

# long-tailed shard sizes, the shape Dirichlet alpha<=0.1 produces:
# a few heavy clients, a long tail of tiny ones
SKEWED = [530, 410, 61, 55, 48, 40, 33, 29, 21, 17, 13, 11, 9, 7, 5, 3]


def _shards(sizes, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for n in sizes:
        x = rng.standard_normal((n, 8, 8, 1)).astype(np.float32)
        y = rng.integers(0, 4, n)
        out.append((x, y))
    return out


def _assert_bitwise(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _assert_same_math(a, b, ulps=8):
    """Equal up to reduction order: every leaf within ``ulps`` float32
    ulps of its largest magnitude. Bucketing and chunking hand XLA a
    different client count and plan length per compiled program (a
    chunk or bucket of one client, a shorter scan), and the installed
    XLA picks a different dot/reduce order for some of those shapes — a
    1-2 ulp difference per parameter, where a wrong key or member order
    would change the minibatch stream and the params by orders of
    magnitude more (DESIGN.md §13)."""
    eps = float(np.finfo(np.float32).eps)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        y = np.asarray(y)
        tol = ulps * eps * max(1.0, float(np.abs(y).max()))
        np.testing.assert_allclose(np.asarray(x), y, rtol=0, atol=tol)


# ------------------------------------------------------------- bucketing ---

@pytest.mark.parametrize("mode", ["off", "pow2", "quantile"])
def test_bucket_members_is_ordered_partition(mode):
    sizes = SKEWED
    buckets = bucket_members(sizes, 16, mode)
    flat = [i for b in buckets for i in b]
    assert sorted(flat) == list(range(len(sizes)))
    for b in buckets:                      # original order within a bucket
        assert list(b) == sorted(b)
    nb = [-(-n // 16) for n in sizes]
    bmax = [max(nb[i] for i in b) for b in buckets]
    assert bmax == sorted(bmax)            # ascending compile shapes


def test_bucketing_never_changes_minibatch_streams():
    """A client's seeded (idx, mask) stream restricted to valid slots is
    identical whether its plan was padded to the group max (unbucketed)
    or its bucket max (steps_per_epoch override)."""
    sizes, batch, epochs = [37, 21, 130, 5], 16, 2
    seeds = [11, 12, 13, 14]
    for members in bucket_members(sizes, batch, "pow2"):
        nb_bucket = max(-(-sizes[j] // batch) for j in members)
        plan = build_batch_plan([sizes[j] for j in members], batch,
                                epochs=epochs,
                                seeds=[seeds[j] for j in members],
                                steps_per_epoch=nb_bucket)
        for k, j in enumerate(members):
            n = sizes[j]
            x = np.arange(n)[:, None]
            want = [bx[:, 0] for bx, _ in
                    batches(x, np.zeros(n, np.int64), batch,
                            seed=seeds[j], epochs=epochs)]
            got = [plan.idx[k, s][plan.mask[k, s]]
                   for s in range(plan.steps) if plan.mask[k, s].any()]
            assert len(want) == len(got)
            for w, g in zip(want, got):
                np.testing.assert_array_equal(w, g)


def test_bucketing_cuts_step_waste_3x_under_dirichlet_skew():
    """The acceptance bound: on a real Dirichlet alpha=0.1 partition both
    bucketing modes cut fully-masked padding steps >= 3x vs one plan (and
    pow2 holds the bound at m=100, where the long tail is longest)."""
    from repro.data.partition import dirichlet_partition
    y = np.random.default_rng(0).integers(0, 10, 20000)
    sizes16 = [max(1, len(p)) for p in dirichlet_partition(y, 16, 0.1,
                                                           seed=0)]
    base = plan_step_waste(sizes16, 16, "off")
    assert base > 0.3                      # single plan is mostly padding
    for mode in ("pow2", "quantile"):
        w = plan_step_waste(sizes16, 16, mode)
        assert w <= base / 3.0, (mode, w, base)
    sizes100 = [max(1, len(p)) for p in dirichlet_partition(y, 100, 0.1,
                                                            seed=0)]
    base100 = plan_step_waste(sizes100, 16, "off")
    assert plan_step_waste(sizes100, 16, "pow2") <= base100 / 3.0


def test_plan_step_waste_off_is_exact():
    # nb = [3, 2, 1], padded to 3 each: 9 scheduled, 6 real
    assert plan_step_waste([33, 17, 2], 16, "off") == pytest.approx(1 / 3)


def test_dirichlet_partition_terminates_at_m1000():
    """The partitioner's min-size rejection loop is infeasible at
    m=1000/alpha=0.1 (the all-clients-fed event ~never happens); the
    bounded-retry + deterministic repair must terminate, respect the
    floor, and still produce an exact index partition."""
    from repro.data.partition import dirichlet_partition
    y = np.random.default_rng(0).integers(0, 4, 8000)
    parts = dirichlet_partition(y, 1000, 0.1, seed=0)
    sizes = [len(p) for p in parts]
    assert min(sizes) >= 2 and sum(sizes) == 8000
    assert len(set(np.concatenate(parts).tolist())) == 8000
    with pytest.raises(ValueError):
        dirichlet_partition(y[:100], 1000, 0.1)


# --------------------------------------- bucketed/chunked local update ----

def test_bucketed_chunked_local_update_is_bitwise():
    """bucketing + chunking are execution-shape knobs only: trained
    params come back equal to the single-plan path up to reduction
    order (``_assert_same_math``), in original member order."""
    sizes = [37, 21, 130, 5, 64, 12]
    shards = _shards(sizes, seed=3)
    seeds = list(range(20, 26))
    inits = [cnn_init(jax.random.PRNGKey(i), SPEC) for i in range(6)]
    counts = np.stack([np.bincount(y, minlength=4) for _, y in shards])

    def run(bucketing, chunk):
        return local_update_bucketed(
            lambda j: inits[j], SPEC, shards, batch_size=16, epochs=2,
            seeds=seeds, use_ldam=False, num_classes=4,
            class_counts=counts, bucketing=bucketing, chunk=chunk)

    ref = run("off", None)
    for bucketing, chunk in (("off", 2), ("pow2", None), ("pow2", 2),
                             ("quantile", 3)):
        _assert_same_math(run(bucketing, chunk), ref)


# ------------------------------------------------------- chunked stacking --

def test_stack_grouped_chunked_is_bitwise():
    clients = [dataclasses.replace(
        _client(i), n_data=10) for i in range(5)]
    _, full = stack_grouped(clients)
    _, chunked = stack_grouped(clients, chunk=2)
    _assert_bitwise(full, chunked)


def _client(i, spec=SPEC, n_data=10):
    from repro.core.ensemble import Client
    return Client(spec=spec, params=cnn_init(jax.random.PRNGKey(i), spec),
                  n_data=n_data)


# -------------------------------------------------------- chunked teacher --

@pytest.mark.parametrize("with_stats", [False, True])
def test_chunked_teacher_matches_unchunked(with_stats):
    clients = [_client(i) for i in range(5)]
    gspecs, gparams = stack_grouped(clients)
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((6, 8, 8, 1)).astype(np.float32))
    ref = grouped_ensemble_logits(gspecs, gparams, x,
                                  with_bn_stats=with_stats)
    for chunk in (1, 2, 3, 5, 16):
        got = grouped_ensemble_logits(gspecs, gparams, x,
                                      with_bn_stats=with_stats,
                                      chunk=chunk)
        if with_stats:
            lg, st = got
            lr, sr = ref
            np.testing.assert_allclose(np.asarray(lg), np.asarray(lr),
                                       atol=1e-5)
            for sa, sb in zip(st, sr):
                for da, db in zip(sa, sb):
                    for f in da:
                        np.testing.assert_allclose(
                            np.asarray(da[f]), np.asarray(db[f]),
                            atol=1e-5)
        else:
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       atol=1e-5)


def test_chunked_teacher_matches_listwise_reference():
    clients = [_client(i) for i in range(4)]
    gspecs, gparams = stack_grouped(clients)
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.standard_normal((3, 8, 8, 1)).astype(np.float32))
    want = ensemble_logits([c.spec for c in clients],
                           [c.params for c in clients], x)
    got = grouped_ensemble_logits(gspecs, gparams, x, chunk=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5)


def test_chunked_teacher_grads_match():
    """Differentiating through the scanned/checkpointed chunk loop gives
    the same generator-side gradients as the one-shot stacked forward."""
    clients = [_client(i) for i in range(5)]
    gspecs, gparams = stack_grouped(clients)
    rng = np.random.default_rng(9)
    x0 = jnp.asarray(rng.standard_normal((4, 8, 8, 1)).astype(np.float32))

    def loss(x, chunk):
        lg = grouped_ensemble_logits(gspecs, gparams, x, chunk=chunk)
        return jnp.sum(jax.nn.log_softmax(lg) ** 2)

    g_ref = jax.grad(loss)(x0, None)
    g_chk = jax.grad(loss)(x0, 2)
    np.testing.assert_allclose(np.asarray(g_chk), np.asarray(g_ref),
                               atol=1e-5)


# ------------------------------------------------------------ tree fedavg --

def test_tree_fedavg_matches_flat():
    rng = np.random.default_rng(10)
    m = 13
    stacked = {"w": jnp.asarray(rng.standard_normal((m, 5, 3)),
                                jnp.float32),
               "b": jnp.asarray(rng.standard_normal((m, 3)), jnp.float32)}
    n_data = rng.integers(1, 500, m).tolist()
    flat = fedavg_stacked(stacked, n_data)
    for branch in (2, 3, 8, 16):
        tree = fedavg_stacked(stacked, n_data, mode="tree", branch=branch)
        for a, b in zip(jax.tree.leaves(flat), jax.tree.leaves(tree)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)


def test_tree_fedavg_respects_survivor_mask():
    rng = np.random.default_rng(11)
    m = 9
    stacked = {"w": jnp.asarray(rng.standard_normal((m, 4)), jnp.float32)}
    n_data = rng.integers(1, 100, m).tolist()
    mask = np.array([True, False, True, True, True, False, True, True,
                     True])
    flat = fedavg_stacked(stacked, n_data, survivor_mask=mask)
    tree = fedavg_stacked(stacked, n_data, survivor_mask=mask,
                          mode="tree", branch=4)
    np.testing.assert_allclose(np.asarray(tree["w"]),
                               np.asarray(flat["w"]), atol=1e-6)


def test_fedavg_unknown_mode_raises():
    stacked = {"w": jnp.ones((2, 3))}
    with pytest.raises(ValueError):
        fedavg_stacked(stacked, [1, 1], mode="nope")


# ----------------------------------- survivor masks compose with buckets ---

def test_quarantine_composes_with_bucketed_training():
    """admit_uploads survivor masks act on the ORIGINAL member order the
    bucketed engine restores, so masked fedavg over a bucketed+chunked
    federation == masked fedavg over the single-plan federation, up to
    reduction order (``_assert_same_math``)."""
    m = 6
    sizes = [37, 21, 130, 5, 64, 12]
    shards = _shards(sizes, seed=13)
    specs = [SPEC] * m
    keys = list(jax.random.split(jax.random.PRNGKey(0), m))
    seeds = list(range(m))
    kw = dict(epochs=1, lr=0.05, momentum=0.9, batch_size=16,
              use_ldam=False, num_classes=4, seeds=seeds, init_keys=keys)
    pol = resolve_exec_policy(DenseExperimentConfig(
        plan_bucketing="pow2", stack_chunk=2))
    ref = train_clients_grouped(specs, shards, **kw)
    buck = train_clients_grouped(specs, shards, **kw, policy=pol)
    _assert_same_math(ref.grouped[1], buck.grouped[1])

    arrived = np.array([True, True, False, True, True, True])
    aref = admit_uploads(ref, arrived=arrived)
    abuck = admit_uploads(buck, arrived=arrived)
    np.testing.assert_array_equal(aref.survivor_mask, abuck.survivor_mask)
    fa = fedavg_stacked(aref.grouped[1][0], [c.n_data for c in aref],
                        survivor_mask=aref.survivor_mask)
    fb = fedavg_stacked(abuck.grouped[1][0], [c.n_data for c in abuck],
                        survivor_mask=abuck.survivor_mask, mode="tree",
                        branch=2)
    for a, b in zip(jax.tree.leaves(fa), jax.tree.leaves(fb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6)


# ------------------------------------------------------------ m=100 smoke --

@pytest.mark.slow
def test_m100_federation_smoke():
    """A m=100 skewed federation runs the whole scaled local phase —
    quantile buckets, chunk-16 group setup, tree fedavg, chunked
    teacher — and stays equivalent to the flat reductions. This is the
    CI forced-8-device scale smoke (ci.yml sets
    xla_force_host_platform_device_count=8)."""
    m = 100
    rng = np.random.default_rng(42)
    sizes = np.maximum(3, (rng.pareto(1.5, m) * 20).astype(int)).tolist()
    shards = _shards(sizes, seed=17)
    specs = [SPEC] * m
    keys = list(jax.random.split(jax.random.PRNGKey(1), m))
    pol = resolve_exec_policy(DenseExperimentConfig(
        plan_bucketing="quantile", stack_chunk=16, fedavg_mode="tree",
        fedavg_branch=8, teacher_chunk=16))
    clients = train_clients_grouped(
        specs, shards, epochs=1, lr=0.05, momentum=0.9, batch_size=16,
        use_ldam=False, num_classes=4, seeds=list(range(m)),
        init_keys=keys, policy=pol)
    gspecs, gparams = clients.grouped
    assert gspecs == ((SPEC, m),)
    assert all(np.isfinite(np.asarray(a)).all()
               for a in jax.tree.leaves(gparams))

    n_data = [c.n_data for c in clients]
    flat = fedavg_stacked(gparams[0], n_data)
    tree = fedavg_stacked(gparams[0], n_data, mode="tree",
                          branch=pol.fedavg_branch)
    for a, b in zip(jax.tree.leaves(flat), jax.tree.leaves(tree)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5)

    x = jnp.asarray(np.random.default_rng(5).standard_normal(
        (4, 8, 8, 1)).astype(np.float32))
    full = grouped_ensemble_logits(gspecs, gparams, x)
    chunked = grouped_ensemble_logits(gspecs, gparams, x,
                                      chunk=pol.teacher_chunk)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(full),
                               atol=1e-4)
