"""Driver of the ``stage2`` traffic: DENSE stage-2 distillation through
``core.train_dense_server``, as the execution policy resolves it on the
device (on a TPU: the fused chunk driver and the fused ``distill_kl``).

Set-up makes the client ensemble and the student on the device from the
seed, then starts one ``train_dense_server`` call. Its first chunk of
``loop_chunk`` epochs (trace, compile-cache load, warm-up) is set-up;
what that chunk produced is what ``correct`` compares. The window
is the same call's next whole chunks, timed at the chunk boundaries
through ``eval_fn``, and it ends by raising out of ``eval_fn`` at the
boundary the window's rule picks.
"""
from __future__ import annotations

import time

import numpy as np

from harness import common, reference as R


# the numbers of ``reference_gap`` that decide ``correct``; the others
# are printed, and wait for readings on the chip to be given limits
COMPARED = ("student_change_gap",)


class WindowClosed(Exception):
    """Raised from ``eval_fn`` to end the call at a chunk boundary."""


def make_models(cfg: dict, key):
    """Client ensemble (grouped by architecture, first occurrence first)
    and student, made on the device in one jitted call. Each client's
    BN running stats are drawn away from init, as a trained client's
    are, so that L_BN has work to do; stage-2 cost depends on the shapes
    alone."""
    import jax
    import jax.numpy as jnp
    kinds = list(cfg["client_kinds"])
    groups: dict[str, list[int]] = {}
    for i, k in enumerate(kinds):
        groups.setdefault(k, []).append(i)
    shape_kw = dict(num_classes=cfg["num_classes"], in_ch=cfg["in_ch"],
                    image_size=cfg["image_size"])

    def client(k, kind):
        ki, ks = jax.random.split(k)
        p = R.model_init(ki, kind, **shape_kw)
        leaves, tree = jax.tree_util.tree_flatten_with_path(p)
        keys = jax.random.split(ks, len(leaves))
        out = []
        for (path, a), kk in zip(leaves, keys):
            name = getattr(path[-1], "key", None)
            if name == "mean":
                a = 0.1 * jax.random.normal(kk, a.shape)
            elif name == "var":
                a = jax.random.uniform(kk, a.shape, minval=0.5, maxval=1.5)
            out.append(a)
        return jax.tree_util.tree_unflatten(tree, out)

    @jax.jit
    def build(key):
        kc, ks = jax.random.split(key)
        ck = jax.random.split(kc, len(kinds))
        out = []
        for kind, idx in groups.items():
            ps = [client(ck[i], kind) for i in idx]
            out.append(ps[0] if len(ps) == 1 else
                       jax.tree.map(lambda *a: jnp.stack(a), *ps))
        return out, R.model_init(ks, cfg["global_kind"], **shape_kw)

    gparams, student = build(key)
    return groups, gparams, student


def prepare(cfg: dict, traffic: dict, seed: int) -> dict:
    """What one run hands the program: its config, the federation as
    the grouped engine leaves it (per-client views plus the stacked
    groups) and the student, all from the seed."""
    import jax
    from repro.core.ensemble import Client
    from repro.fl.federation import ClientList
    from repro.models.cnn import CNNSpec
    w_models, w_key = common.seed_words(seed, 2)
    groups, gparams, student = make_models(cfg, jax.random.PRNGKey(w_models))
    size = (cfg["synth_batch"], cfg["image_size"], cfg["image_size"],
            cfg["in_ch"])
    probe = jax.random.uniform(jax.random.fold_in(
        jax.random.PRNGKey(w_key), 1), size, minval=-1.0, maxval=1.0)
    specs = {k: CNNSpec(kind=k, num_classes=cfg["num_classes"],
                        in_ch=cfg["in_ch"], width=cfg["width"],
                        image_size=cfg["image_size"]) for k in groups}
    views = [None] * cfg["n_clients"]
    for (kind, idx), gp in zip(groups.items(), gparams):
        for j, i in enumerate(idx):
            views[i] = Client(spec=specs[kind], params=gp if len(idx) == 1
                              else jax.tree.map(lambda a, _j=j: a[_j], gp))
    clients = ClientList(views, [(specs[k], len(i)) for k, i in
                                 groups.items()], gparams)
    scfg = common.program_config(
        cfg, epochs=cfg["loop_chunk"] * traffic["max_chunks"])
    return {"scfg": scfg, "clients": clients,
            "student": student, "start": jax.device_get(student),
            "key": jax.random.PRNGKey(w_key), "probe": probe}


def drive(st: dict, on_boundary) -> dict:
    """One ``train_dense_server`` call; ``on_boundary()`` runs at every
    chunk boundary and returns False to end the call there. -> what the
    first chunk produced: the student (a host copy: the call donates its
    buffers) and each epoch's (last generator loss, student loss) as the
    call's own history records them."""
    import jax
    from repro.core import dense, train_dense_server
    got, made, history = {}, [], dense.DenseHistory

    def record():
        made.append(history())
        return made[-1]

    def eval_fn(params, spec):
        if "student" not in got:
            h = made[-1]
            got["student"] = jax.device_get(params)
            got["losses"] = np.array([h.gen_loss, h.dis_loss], np.float64).T
        if not on_boundary():
            raise WindowClosed
        return 0.0

    scfg = st["scfg"]
    dense.DenseHistory = record
    try:
        train_dense_server(st["key"], st["clients"], scfg,
                           student_params=st.pop("student"),
                           eval_fn=eval_fn, eval_every=scfg.loop_chunk)
    except WindowClosed:
        pass
    finally:
        dense.DenseHistory = history
    return got


def reference(cfg: dict, st: dict, **variant) -> dict:
    """The plain reference's first chunk from the program's start
    (``reference.stage2_reference``); ``variant`` makes it one of its
    variants."""
    clients = [c.params for c in st["clients"]]
    scfg = st["scfg"]
    return R.stage2_reference(cfg, clients, st["start"], st["key"],
                              scfg.loop_chunk, n_keys=scfg.epochs,
                              **variant)


def reference_gap(cfg: dict, st: dict, program: dict, ref: dict) -> dict:
    """What the program's first chunk produced (``drive``, or a variant
    of the reference put in its place) against the reference's.

    -> ``student_change_gap`` (the median leaf's gap of change norms,
    ``common.change_gap``), ``student_change_worst`` (its worst leaf),
    ``student_logit_gap`` (the student's logit change on probe images
    against the reference's, ``common.logit_gap``) and ``loss_gap`` (the
    first epoch's losses, ``common.loss_gap``); ``COMPARED`` names those
    that decide ``correct``."""
    import jax
    start = jax.device_get(program.get("start", st["start"]))
    student = jax.device_get(program["student"])
    ref_student = jax.device_get(ref["student"])
    logits = [R.student_logits(p, cfg["global_kind"], st["probe"])
              for p in (start, student, ref_student)]
    change = common.change_gap(start, student, ref_student,
                               jax.device_get(ref["grad0"]))
    return {"student_change_gap": change["median"],
            "student_change_worst": change["gap"],
            "worst_leaf": change["leaf"],
            "student_logit_gap": common.logit_gap(*logits),
            "loss_gap": common.loss_gap(program["losses"], ref["losses"]),
            "losses": np.asarray(program["losses"]).tolist(),
            "ref_losses": ref["losses"].tolist()}


def run(ctx):
    cfg = ctx.cfg
    st = prepare(cfg, ctx.traffic, ctx.seed)
    window = common.Window(ctx.seconds)

    def on_boundary():
        if not window.marks:
            ctx.start_window()
            return window.mark()
        with common.span("eval_hook"):
            more = window.mark()
        ctx.unit_done()
        return more and not ctx.tracing

    got = drive(st, on_boundary)
    ctx.end_window()
    epochs = window.units * st["scfg"].loop_chunk
    ctx.unit_rate = epochs / window.seconds_measured
    ctx.e2e["stage2_epochs_per_s"] = ctx.unit_rate
    ctx.attempted = ctx.window_units = epochs
    ctx.read_memory()
    # the reference, once the window has closed and the program's state
    # is freed; it takes the benchmark's own weights, not the program's
    t0 = time.perf_counter()
    numbers = reference_gap(cfg, st, got, reference(cfg, st))
    ctx.reference_s = time.perf_counter() - t0
    for name in COMPARED:
        ctx.compare(name, numbers[name])
    ctx.details["reference"] = numbers
    ctx.non_finite = common.non_finite(got["student"])
    return ctx
