"""Equivalence tests for the perf fast paths: grouped-vmap ensemble,
fused (device-resident) epoch driver, and the batched evaluate.
Optimizations must never change the math."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.paper_cifar import DenseExperimentConfig
from repro.core import losses as LS
from repro.core import train_dense_server
from repro.core.dense import _chunk_bounds, evaluate
from repro.core.ensemble import (Client, ensemble_logits,
                                 grouped_ensemble_logits, group_clients,
                                 split_clients, stack_grouped,
                                 stack_homogeneous)
from repro.models.cnn import (_GROUPED_IM2COL_MAX_B, _GROUPED_UNROLL_MAX_M,
                              CNNSpec, cnn_init, cnn_logits)


def _mk_clients(kinds, seed0=0, width=0.5, **spec_kw):
    clients = []
    for i, k in enumerate(kinds):
        sp = CNNSpec(kind=k, num_classes=6, in_ch=3, width=width,
                     image_size=16, **spec_kw)
        clients.append(Client(spec=sp,
                              params=cnn_init(jax.random.PRNGKey(seed0 + i),
                                              sp)))
    return clients


# ------------------------------------------------------------- grouping ---

def test_group_clients_insertion_ordered_partition():
    kinds = ("cnn1", "cnn2", "cnn1", "wrn16_1", "cnn2", "cnn1")
    clients = _mk_clients(kinds)
    groups = group_clients(clients)
    # deterministic key order: first-occurrence order of each spec
    assert [spec.kind for spec, _ in groups] == ["cnn1", "cnn2", "wrn16_1"]
    assert [idx for _, idx in groups] == [(0, 2, 5), (1, 4), (3,)]
    # exact partition of client indices
    flat = [i for _, idx in groups for i in idx]
    assert sorted(flat) == list(range(len(kinds)))


def test_stack_homogeneous_via_groups():
    clients = _mk_clients(("cnn1",) * 3)
    spec, stacked = stack_homogeneous(clients)
    assert spec == clients[0].spec
    lead = jax.tree.leaves(stacked)[0].shape[0]
    assert lead == 3
    with pytest.raises(AssertionError):
        stack_homogeneous(_mk_clients(("cnn1", "cnn2")))


@pytest.mark.parametrize("batch", [8, 64])  # im2col and conv/scan regimes
def test_grouped_matches_unrolled_mixed_architectures(batch):
    kinds = ("cnn1", "cnn2", "cnn1", "wrn16_1", "cnn2")
    clients = _mk_clients(kinds)
    x = jax.random.normal(jax.random.PRNGKey(42), (batch, 16, 16, 3))
    specs, cparams = split_clients(clients)
    gspecs, gparams = stack_grouped(clients)
    assert sum(n for _, n in gspecs) == len(clients)
    ref, ref_stats = ensemble_logits(specs, cparams, x, with_bn_stats=True)
    got, got_stats = grouped_ensemble_logits(gspecs, gparams, x,
                                             with_bn_stats=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)
    # L_BN consumes the stats as an order-invariant sum over clients
    np.testing.assert_allclose(float(LS.bn_loss(got_stats)),
                               float(LS.bn_loss(ref_stats)), rtol=1e-4)


# group sizes on both sides of the unroll bound: an unrolled scan, and
# lax.map (at smaller widths, to keep the larger group cheap)
RESIDUAL_GROUPS = pytest.mark.parametrize(
    "m,width", [(3, 0.5), (_GROUPED_UNROLL_MAX_M + 1, 0.25)],
    ids=["unrolled", "map"])


@RESIDUAL_GROUPS
@pytest.mark.parametrize("batch", [4, 32])  # both sides of the B rule
@pytest.mark.parametrize("kind", ["wrn16_1", "resnet18"])
def test_grouped_residual_stack_matches_unrolled(kind, batch, m, width):
    """Size->=2 residual groups run the stacked forward
    (models.cnn._grouped_resnet_map) at every batch size, unrolled up to
    _GROUPED_UNROLL_MAX_M clients and as lax.map above: logits and L_BN
    inputs must match the unrolled reference — including the
    projection-shortcut stats slots — and so must the stats-free
    eval-only branch."""
    clients = _mk_clients((kind,) * m, width=width)
    x = jax.random.normal(jax.random.PRNGKey(3), (batch, 16, 16, 3))
    specs, cparams = split_clients(clients)
    gspecs, gparams = stack_grouped(clients)
    ref, ref_stats = ensemble_logits(specs, cparams, x, with_bn_stats=True)
    got, got_stats = grouped_ensemble_logits(gspecs, gparams, x,
                                             with_bn_stats=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=5e-4)
    np.testing.assert_allclose(float(LS.bn_loss(got_stats)),
                               float(LS.bn_loss(ref_stats)), rtol=1e-3)
    # eval-only path (no stats) agrees too
    got_e = grouped_ensemble_logits(gspecs, gparams, x)
    ref_e = ensemble_logits(specs, cparams, x)
    np.testing.assert_allclose(np.asarray(got_e), np.asarray(ref_e),
                               atol=5e-4)


@RESIDUAL_GROUPS
@pytest.mark.parametrize("chunk", [None, 2])
def test_grouped_residual_input_grad_matches_unrolled(chunk, m, width):
    """The generator step's path through the teacher: d(L_CE + L_BN)/dx
    of a stacked ResNet group at B = 32 matches the unrolled reference
    — unchunked, and chunked with a remainder (an odd group in chunks of
    2)."""
    clients = _mk_clients(("resnet18",) * m, width=width)
    x = jax.random.normal(jax.random.PRNGKey(5), (32, 16, 16, 3))
    y = jnp.arange(x.shape[0]) % 6
    specs, cparams = split_clients(clients)
    gspecs, gparams = stack_grouped(clients)

    def loss(avg, stats):
        return LS.ce_loss(avg, y) + LS.bn_loss(stats)

    g_ref = jax.grad(lambda xx: loss(*ensemble_logits(
        specs, cparams, xx, with_bn_stats=True)))(x)
    g_got = jax.grad(lambda xx: loss(*grouped_ensemble_logits(
        gspecs, gparams, xx, with_bn_stats=True, chunk=chunk)))(x)
    scale = float(jnp.max(jnp.abs(g_ref)))
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref),
                               atol=1e-3 * scale)


@pytest.mark.parametrize("kind,batch,m,name", [
    ("cnn1", 4, 2, "ensemble.grouped_im2col"),
    ("cnn1", _GROUPED_IM2COL_MAX_B, 2, "ensemble.grouped_native"),
    ("wrn16_1", 4, 2, "ensemble.grouped_unrolled"),
    ("wrn16_1", 4, _GROUPED_UNROLL_MAX_M + 1, "ensemble.grouped_native")])
def test_grouped_regime_counters(kind, batch, m, name):
    """repro.obs counts, per stacked group traced, which formulation the
    grouped forward took: conv stacks im2col below
    _GROUPED_IM2COL_MAX_B and native convolutions from it on, residual
    kinds native convolutions at every batch, unrolled up to
    _GROUPED_UNROLL_MAX_M clients and under lax.map above."""
    clients = _mk_clients((kind,) * m)
    gspecs, gparams = stack_grouped(clients)
    obs.reset()
    x = jnp.zeros((batch, 16, 16, 3))
    jax.jit(lambda gp, xx: grouped_ensemble_logits(gspecs, gp, xx))(
        gparams, x)
    assert obs.snapshot()["counters"] == {name: 1}
    obs.reset()


@pytest.mark.parametrize("m,loops", [(3, False),
                                     (_GROUPED_UNROLL_MAX_M + 1, True)])
def test_grouped_residual_unroll_structure(m, loops):
    """The generator step's input gradient through a stacked residual
    group lowers with no loop up to _GROUPED_UNROLL_MAX_M clients — a
    loop under jax.grad stacks every client's residuals — and with one
    above the bound."""
    clients = _mk_clients(("wrn16_1",) * m, width=0.25)
    gspecs, gparams = stack_grouped(clients)
    x = jnp.zeros((4, 16, 16, 3))

    def loss(xx):
        avg, stats = grouped_ensemble_logits(gspecs, gparams, xx,
                                             with_bn_stats=True)
        return jnp.sum(avg ** 2) + LS.bn_loss(stats)

    text = jax.jit(jax.grad(loss)).lower(x).as_text()
    assert ("stablehlo.while" in text) == loops


def test_grouped_matches_under_jit_homogeneous():
    clients = _mk_clients(("cnn1",) * 6)
    x = jax.random.normal(jax.random.PRNGKey(7), (16, 16, 16, 3))
    specs, cparams = split_clients(clients)
    gspecs, gparams = stack_grouped(clients)
    ref = jax.jit(lambda cp: ensemble_logits(specs, cp, x))(cparams)
    got = jax.jit(lambda gp: grouped_ensemble_logits(gspecs, gp, x))(gparams)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


# ----------------------------------------------------------- epoch driver ---

SCFG = DenseExperimentConfig(
    n_clients=2, alpha=0.5, local_epochs=1, batch_size=32, num_classes=4,
    image_size=8, in_ch=1, train_per_class=16, test_per_class=8,
    client_kinds=("cnn1", "cnn1"), global_kind="cnn1", width=0.25, nz=16,
    t_g=2, epochs=5, synth_batch=16, s_steps=2, loop_chunk=2)


def test_chunk_bounds():
    assert _chunk_bounds(10, 4, 0) == [(0, 4), (4, 8), (8, 10)]
    assert _chunk_bounds(10, 4, 3) == [(0, 3), (3, 6), (6, 9), (9, 10)]
    assert _chunk_bounds(4, 8, 0) == [(0, 4)]


def test_fused_and_python_drivers_agree():
    """loop_mode='fused' must be a pure perf choice: same student params,
    same metric history as the per-step python driver for the same key
    (both consume the identical per-epoch key stream).

    Same math, not the same bits: the fused driver compiles a whole chunk
    of epochs as one program, the python driver one program per step, and
    XLA fuses and orders the reductions of the two differently. Epoch 0
    therefore agrees to about one ulp — a key-stream or ordering bug
    would miss by orders of magnitude more. Later epochs drift further:
    the generator's Adam step divides by sqrt(v) and amplifies a rounding
    difference in a near-zero gradient, and that reaches the student
    through the synthetic batch (DESIGN.md §11)."""
    clients = []
    sp = CNNSpec(kind="cnn1", num_classes=SCFG.num_classes, in_ch=SCFG.in_ch,
                 width=SCFG.width, image_size=SCFG.image_size)
    for i in range(2):
        clients.append(Client(spec=sp, params=cnn_init(jax.random.PRNGKey(i),
                                                       sp)))
    outs = {}
    for mode in ("python", "fused"):
        scfg = dataclasses.replace(SCFG, loop_mode=mode)
        stu, gen, hist = train_dense_server(jax.random.PRNGKey(3), clients,
                                            scfg)
        outs[mode] = (stu, gen, hist)
    stu_p, _, hist_p = outs["python"]
    stu_f, _, hist_f = outs["fused"]
    assert len(hist_f.gen_loss) == len(hist_p.gen_loss) == SCFG.epochs
    np.testing.assert_allclose(hist_f.gen_loss[0], hist_p.gen_loss[0],
                               rtol=1e-6)
    np.testing.assert_allclose(hist_f.dis_loss[0], hist_p.dis_loss[0],
                               rtol=1e-6)
    for a, b in zip(jax.tree.leaves(stu_p), jax.tree.leaves(stu_f)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
    np.testing.assert_allclose(hist_f.gen_loss, hist_p.gen_loss, rtol=1e-3,
                               atol=1e-5)
    np.testing.assert_allclose(hist_f.dis_loss, hist_p.dis_loss, rtol=1e-3,
                               atol=1e-5)
    for pp, pf in zip(hist_p.gen_parts, hist_f.gen_parts):
        assert set(pp) == set(pf) == {"ce", "bn", "div"}
        for k in pp:
            np.testing.assert_allclose(pf[k], pp[k], rtol=1e-3, atol=1e-5)


def test_fused_eval_every_alignment():
    clients = []
    sp = CNNSpec(kind="cnn1", num_classes=SCFG.num_classes, in_ch=SCFG.in_ch,
                 width=SCFG.width, image_size=SCFG.image_size)
    for i in range(2):
        clients.append(Client(spec=sp, params=cnn_init(jax.random.PRNGKey(i),
                                                       sp)))
    seen = []

    def eval_fn(params, spec):
        seen.append(1)
        return 0.5

    scfg = dataclasses.replace(SCFG, loop_mode="fused", epochs=4,
                               loop_chunk=3)
    _, _, hist = train_dense_server(jax.random.PRNGKey(0), clients, scfg,
                                    eval_fn=eval_fn, eval_every=2)
    assert [e for e, _ in hist.acc] == [2, 4]


def test_unknown_loop_mode_raises():
    sp = CNNSpec(kind="cnn1", num_classes=4, in_ch=1, width=0.25,
                 image_size=8)
    clients = [Client(spec=sp, params=cnn_init(jax.random.PRNGKey(0), sp))]
    scfg = dataclasses.replace(SCFG, loop_mode="nope")
    with pytest.raises(ValueError):
        train_dense_server(jax.random.PRNGKey(0), clients, scfg)


# -------------------------------------------------------------- evaluate ---

def test_evaluate_matches_naive_loop():
    sp = CNNSpec(kind="cnn1", num_classes=5, in_ch=3, width=0.5,
                 image_size=8)
    params = cnn_init(jax.random.PRNGKey(0), sp)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((37, 8, 8, 3)).astype(np.float32)
    y = rng.integers(0, 5, 37)
    # naive reference: per-batch python loop + per-batch sync
    correct = 0
    for i in range(0, 37, 16):
        lg = cnn_logits(params, sp, jnp.asarray(x[i:i + 16]))
        correct += int(jnp.sum(jnp.argmax(lg, -1) == jnp.asarray(y[i:i + 16])))
    want = correct / 37
    got = evaluate(params, sp, x, y, batch=16)
    assert got == pytest.approx(want)
    # batch larger than the dataset: single padded batch
    assert evaluate(params, sp, x, y, batch=512) == pytest.approx(want)
    # multiple device chunks (memory-bounded path): 3 batches, chunk=2
    assert evaluate(params, sp, x, y, batch=16,
                    device_batches=2) == pytest.approx(want)
