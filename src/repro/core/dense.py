"""DENSE two-stage server training (Algorithm 1).

Stage 1 (data generation): T_G generator steps per epoch minimizing
L_gen = L_CE + λ1 L_BN + λ2 L_div against the frozen client ensemble and
the *current* student (whose decision boundary defines L_div).

Stage 2 (model distillation): a student step on the same synthetic batch
minimizing KL(D(x̂) ‖ f_S(x̂)).

Faithful to Algorithm 1 by default (one noise batch per epoch, one student
step). ``s_steps > 1`` / ``replay=True`` are beyond-paper extensions kept
off unless asked for (EXPERIMENTS.md reports them separately).

Fast-path design
----------------
The frozen ensemble is held in the grouped-vmap representation
(ensemble.stack_grouped): clients are grouped by CNNSpec at
``make_dense_steps`` setup and each group is evaluated with a single
vmapped forward, so the per-step ensemble cost is O(#architectures), not
O(#clients).

The epoch driver is selected by the resolved execution policy
(``configs.backend.resolve_exec_policy``; ``scfg.loop_mode`` when set,
else the backend registry default — cpu: "python", gpu/tpu: "fused"):

  * ``"python"`` — per-step jit, one host sync (``float``) per
    metric per epoch. Fastest on single-core CPU hosts where the fused
    scan compiles slowly.
  * ``"fused"``  — device-resident: ``scfg.loop_chunk`` epochs are chunked
    into ONE ``jax.lax.scan`` program with donated carry buffers
    (gen/student params + optimizer states never round-trip to host) and
    on-device metric stacking, so the host syncs once per chunk instead
    of 3× per epoch. The win grows with accelerator dispatch latency.

Both modes derive per-epoch PRNG keys identically
(``jax.random.split(key, epochs)`` then kz/ky/ks per epoch), so they
produce the same student up to compilation-order float noise
(tests/test_fastpath.py asserts agreement).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.backend import resolve_exec_policy
from repro.core import generator as G
from repro.core import losses as LS
from repro.core.ensemble import (Client, grouped_ensemble_logits,
                                 stack_grouped)
from repro.models.cnn import CNNSpec, cnn_apply, cnn_logits, cnn_init
from repro import optim


def merge_bn_stats(opt_params, stat_params):
    """Overwrite BN running stats (functional aux output) after an
    optimizer step — they carry no gradient and must not be SGD-updated."""
    def f(path, a, b):
        last = path[-1]
        key = getattr(last, "key", None)
        return b if key in ("mean", "var") else a
    return jax.tree_util.tree_map_with_path(f, opt_params, stat_params)


@dataclass
class DenseHistory:
    gen_loss: list = field(default_factory=list)
    gen_parts: list = field(default_factory=list)
    dis_loss: list = field(default_factory=list)
    acc: list = field(default_factory=list)


def make_dense_steps(clients: Sequence[Client], student_spec: CNNSpec,
                     scfg, *, use_bn: bool = True, use_div: bool = True,
                     mesh=None):
    """Build jitted steps closed over the frozen (grouped) ensemble.

    Returns (gen_step, student_step, g_opt, s_opt, gparams, epoch_step,
    epochs_step): gparams is the grouped-stacked client params
    (ensemble.stack_grouped) that every step takes as its traced ensemble
    argument; epochs_step scans epoch_step over a chunk of per-epoch keys
    with donated carries (the loop_mode="fused" driver).

    mesh defaults to ``fl.sharding.resolve_mesh(scfg)``
    (scfg.ensemble_shard_mode): with a ("clients", "data") mesh the
    stacked client params are placed client-sharded and the teacher's
    logit mean lowers to one psum over the ``clients`` axis
    (ensemble._group_sum_sharded).

    use_bn / use_div=False reproduce the paper's ablations (Table 6).
    """
    # ALL execution modes resolve through the backend registry
    # (configs.backend.resolve_exec_policy, DESIGN.md §11): scfg knobs
    # when set, per-backend defaults otherwise. The stage-2 KL
    # implementation ("ref" jnp autodiff vs "fused" Pallas custom-VJP
    # kernel pair — kernels/distill_kl, DESIGN.md §9) routes both the
    # student's L_dis and the generator's L_div, so the fused dL/dt
    # stream is reused in stage 1.
    pol = resolve_exec_policy(scfg)
    if mesh is None:
        from repro.fl.sharding import resolve_mesh
        mesh = resolve_mesh(pol)
    kl_mode = pol.distill_kl
    if mesh is not None and kl_mode == "fused":
        # the compiler cannot partition a Pallas kernel: on a mesh each
        # device runs the fused KL pair whole on its replicated logits
        # (shard_map's transpose psums the cotangents of replicated
        # operands after dividing the output's by the device count, so
        # the gradients are those of one device)
        from jax.sharding import PartitionSpec as P

        def _kl(fn):
            return jax.shard_map(fn, mesh=mesh, in_specs=P(),
                                 out_specs=P(), check_vma=False)
    else:
        def _kl(fn):
            return fn
    div_loss = _kl(functools.partial(LS.div_loss, mode=kl_mode,
                                     policy=pol))
    distill_loss = _kl(functools.partial(
        LS.distill_loss, mode=kl_mode, with_teacher_grad=False, policy=pol))
    # nan_policy="skip" compiles an isfinite guard into BOTH steps: a
    # non-finite loss (or grad) step becomes a no-op update via
    # jnp.where over the param/opt-state trees. Any other policy
    # compiles the guard out entirely — the healthy path is unchanged.
    nan_guard = getattr(scfg, "nan_policy", "raise") == "skip"
    g_opt = optim.adam(scfg.g_lr)
    s_opt = optim.sgd(scfg.s_lr, momentum=scfg.s_momentum)
    img = scfg.image_size
    # stack_grouped statically slices quarantined clients out when the
    # federation carries admission masks (fl.protocol.admit_uploads):
    # the teacher is built from survivors only, bit-identically to a
    # federation without the quarantined clients. stack_chunk stages the
    # stack through O(chunk) host slices; teacher_chunk streams the
    # stage-1/2 ensemble sum through scanned client slices so the
    # teacher never materializes (m, B, C) activations (DESIGN.md §13).
    t_chunk = pol.teacher_chunk
    gspecs, gparams = stack_grouped(clients, chunk=pol.stack_chunk)
    if mesh is not None:
        from repro.fl.sharding import put_grouped
        gparams = put_grouped(gspecs, gparams, mesh)

    def gen_forward(gen_p, z):
        return G.img_generator(gen_p, z, img_size=img)

    @jax.jit
    def gen_step(gen_p, g_state, stu_p, gparams, z, y):
        def loss_fn(gp):
            with jax.named_scope(obs.GENERATOR):
                x = gen_forward(gp, z)
            with jax.named_scope(obs.TEACHER):
                avg, stats = grouped_ensemble_logits(
                    gspecs, gparams, x, with_bn_stats=True, mesh=mesh,
                    chunk=t_chunk)
            with jax.named_scope(obs.STUDENT):
                stu = cnn_logits(stu_p, student_spec, x)
            with jax.named_scope(obs.LOSS):
                l_ce = LS.ce_loss(avg, y)
                l_bn = LS.bn_loss(stats) if use_bn else jnp.zeros(())
                l_div = div_loss(avg, stu) if use_div else jnp.zeros(())
                total = l_ce + scfg.lambda_bn * l_bn \
                    + scfg.lambda_div * l_div
            return total, {"ce": l_ce, "bn": l_bn, "div": l_div}

        (loss, parts), grads = jax.value_and_grad(loss_fn, has_aux=True)(gen_p)
        with jax.named_scope(obs.GENERATOR):
            new_p, new_state = g_opt.update(grads, g_state, gen_p)
            if nan_guard:
                ok = jnp.isfinite(loss) & \
                    jnp.isfinite(optim.global_norm(grads))
                new_p = jax.tree.map(lambda n, o: jnp.where(ok, n, o),
                                     new_p, gen_p)
                new_state = jax.tree.map(lambda n, o: jnp.where(ok, n, o),
                                         new_state, g_state)
        return new_p, new_state, loss, parts

    @jax.jit
    def student_step(stu_p, s_state, gen_p, gparams, z):
        with jax.named_scope(obs.GENERATOR):
            x = jax.lax.stop_gradient(gen_forward(gen_p, z))
        with jax.named_scope(obs.TEACHER):
            avg = grouped_ensemble_logits(gspecs, gparams, x, mesh=mesh,
                                          chunk=t_chunk)

        def loss_fn(sp):
            with jax.named_scope(obs.STUDENT):
                logits, new_sp, _ = cnn_apply(sp, student_spec, x,
                                              train=True)
            with jax.named_scope(obs.LOSS):
                # avg is stop-gradient'd upstream: skip the fused dL/dt
                # stream
                loss = distill_loss(avg, logits)
            return loss, new_sp

        (loss, stats_p), grads = jax.value_and_grad(loss_fn, has_aux=True)(stu_p)
        with jax.named_scope(obs.STUDENT):
            new_p, new_state = s_opt.update(grads, s_state, stu_p)
            new_p = merge_bn_stats(new_p, stats_p)
            if nan_guard:
                # guards the merged BN stats too: a non-finite synthetic
                # batch would otherwise poison the running mean/var
                ok = jnp.isfinite(loss) & \
                    jnp.isfinite(optim.global_norm(grads))
                new_p = jax.tree.map(lambda n, o: jnp.where(ok, n, o),
                                     new_p, stu_p)
                new_state = jax.tree.map(lambda n, o: jnp.where(ok, n, o),
                                         new_state, s_state)
        return new_p, new_state, loss

    t_g = scfg.t_g
    s_steps = getattr(scfg, "s_steps", 1)
    nz, b, ncls = scfg.nz, scfg.synth_batch, scfg.num_classes

    def _epoch_body(gen_p, g_state, stu_p, s_state, gparams, key):
        """One Algorithm-1 epoch: T_G generator steps (lines 8-11) then
        the distillation step(s) (lines 13-14). Pure-jax; shared by the
        jitted epoch_step and the fused multi-epoch scan. The python
        driver mirrors this key derivation exactly."""
        kz, ky, ks = jax.random.split(key, 3)
        z = jax.random.normal(kz, (b, nz))
        y = jax.random.randint(ky, (b,), 0, ncls)

        def gbody(carry, _):
            gp, gs = carry
            gp, gs, loss, parts = gen_step(gp, gs, stu_p, gparams, z, y)
            return (gp, gs), (loss, parts)

        (gen_p, g_state), (gl, parts) = jax.lax.scan(
            gbody, (gen_p, g_state), None, length=t_g)

        # first student step reuses the epoch's z (Algorithm 1); extra
        # steps (s_steps > 1, beyond-paper) draw fresh noise
        extra = jax.random.normal(ks, (max(s_steps - 1, 0), b, nz))
        zs = jnp.concatenate([z[None], extra], axis=0)

        def sbody(carry, z_i):
            sp, ss = carry
            sp, ss, loss = student_step(sp, ss, gen_p, gparams, z_i)
            return (sp, ss), loss

        (stu_p, s_state), dl = jax.lax.scan(sbody, (stu_p, s_state), zs)
        metrics = {"gen_loss": gl[-1],
                   "parts": jax.tree.map(lambda a: a[-1], parts),
                   "dis_loss": dl[-1]}
        return gen_p, g_state, stu_p, s_state, metrics

    epoch_step = jax.jit(_epoch_body)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def epochs_step(gen_p, g_state, stu_p, s_state, gparams, keys):
        """loop_mode="fused": a chunk of len(keys) epochs as ONE device
        program. Carries are donated (params/opt states stay resident);
        per-epoch metrics are stacked on device and fetched by the caller
        in a single host sync per chunk."""
        def body(carry, key):
            gp, gs, sp, ss = carry
            gp, gs, sp, ss, m = _epoch_body(gp, gs, sp, ss, gparams, key)
            return (gp, gs, sp, ss), m

        (gen_p, g_state, stu_p, s_state), metrics = jax.lax.scan(
            body, (gen_p, g_state, stu_p, s_state), keys)
        return gen_p, g_state, stu_p, s_state, metrics

    return (gen_step, student_step, g_opt, s_opt, gparams, epoch_step,
            epochs_step)


def _chunk_bounds(epochs: int, chunk: int, eval_every: int,
                  ckpt_every: int = 0, start: int = 0):
    """Chunk [start, epochs) into scan programs of <= chunk epochs, never
    crossing an eval or checkpoint boundary (0 disables either kind).
    ``start`` > 0 resumes mid-schedule (checkpoint restore): the bounds
    after a checkpoint boundary are identical whether the run started at
    0 or resumed at that boundary, which is what makes fused-mode resume
    replay the same chunk programs."""
    bounds, e = [], start
    while e < epochs:
        nxt = min(e + chunk, epochs)
        if eval_every:
            nxt = min(nxt, ((e // eval_every) + 1) * eval_every)
        if ckpt_every:
            nxt = min(nxt, ((e // ckpt_every) + 1) * ckpt_every)
        bounds.append((e, nxt))
        e = nxt
    return bounds


def train_dense_server(key, clients: Sequence[Client], scfg,
                       student_spec: CNNSpec | None = None, *,
                       eval_fn: Callable | None = None,
                       use_bn: bool = True, use_div: bool = True,
                       eval_every: int = 0,
                       student_params: dict | None = None,
                       _poison_epochs=(), _stop_after_epoch: int = 0):
    """Run Algorithm 1. Returns (student_params, gen_params, history).

    Execution modes resolve through the backend registry
    (configs.backend.resolve_exec_policy, DESIGN.md §11): scfg knobs
    when set, per-backend defaults otherwise.
    loop_mode selects the epoch driver ("python" per-step jit or
    "fused" device-resident chunks of scfg.loop_chunk epochs; see
    module docstring).
    scfg.ensemble_shard_mode="clients" additionally shards the frozen
    client stack over a ("clients", "data") mesh (fl/sharding.py) — a
    pure placement/lowering choice, same math (DESIGN.md §8); the
    generator, the student, both optimizer states and the epoch keys
    are then placed replicated on the mesh. The ``dense.setup`` span
    carries the mesh's shape (``mesh="clients=4,data=1"``, or "none").
    scfg.distill_kl_mode selects the stage-2 KL implementation ("ref"
    jnp autodiff or "fused" Pallas custom-VJP pair, DESIGN.md §9) —
    also a pure implementation choice, same math.

    Self-healing (DESIGN.md §10). ``scfg.nan_policy`` decides what a
    non-finite generator/student loss means:

      * ``"raise"`` (default) — FloatingPointError at the first bad
        epoch (host-side check of the fetched metrics).
      * ``"skip"`` — the bad *step* is a compiled no-op (isfinite guard
        inside the jitted steps, make_dense_steps); training continues.
      * ``"rollback"`` — restore the last good host snapshot: epoch
        granularity under the python driver, chunk granularity under the
        fused driver (the whole bad chunk's epochs are dropped; carries
        are copied before the donated scan).

    Checkpoint/resume. With ``scfg.checkpoint_every`` > 0 and
    ``scfg.checkpoint_path`` set, the FULL server state (gen/student
    params, both optimizer states, the base epoch-key and the epoch
    index) is written through checkpoint/io.py every N epochs, and an
    existing checkpoint at that path is restored on entry. Both drivers
    re-derive ``epoch_keys`` from the restored base key, so a killed run
    resumes bit-identically (tests/test_checkpoint.py); history covers
    only post-resume epochs.

    ``_poison_epochs`` / ``_stop_after_epoch`` are test-only fault hooks:
    NaN-fill the listed epochs' latent batch (python driver), and return
    early after N epochs to simulate a mid-run kill.
    """
    from repro.checkpoint import (checkpoint_exists, restore_checkpoint,
                                  save_checkpoint)

    student_spec = student_spec or CNNSpec(
        kind=scfg.global_kind, num_classes=scfg.num_classes,
        in_ch=scfg.in_ch, width=scfg.width, image_size=scfg.image_size)
    nan_policy = getattr(scfg, "nan_policy", "raise")
    if nan_policy not in ("raise", "skip", "rollback"):
        raise ValueError(f"unknown nan_policy {nan_policy!r} "
                         "(expected 'raise', 'skip' or 'rollback')")
    ck_every = int(getattr(scfg, "checkpoint_every", 0) or 0)
    ck_path = getattr(scfg, "checkpoint_path", "") or ""
    ckpt_on = bool(ck_every and ck_path)
    start_epoch = 0
    from repro.fl.sharding import put_replicated, resolve_mesh
    mesh = resolve_mesh(resolve_exec_policy(scfg))
    mesh_shape = "none" if mesh is None else ",".join(
        f"{a}={n}" for a, n in mesh.shape.items())
    with obs.span("dense.setup", mesh=mesh_shape):
        with obs.span("dense.make_steps"):
            (gen_step, student_step, g_opt, s_opt, gparams, epoch_step,
             epochs_step) = make_dense_steps(clients, student_spec, scfg,
                                             use_bn=use_bn, use_div=use_div,
                                             mesh=mesh)
        with obs.span("dense.init"):
            k_gen, k_stu, key = jax.random.split(key, 3)
            gen_p = G.img_generator_init(k_gen, nz=scfg.nz,
                                         img_size=scfg.image_size,
                                         out_ch=scfg.in_ch)
            stu_p = student_params if student_params is not None \
                else cnn_init(k_stu, student_spec)
            g_state = g_opt.init(gen_p)
            s_state = s_opt.init(stu_p)
        if ckpt_on and checkpoint_exists(ck_path):
            with obs.span("dense.restore"):
                like = {"gen_p": gen_p, "g_state": g_state, "stu_p": stu_p,
                        "s_state": s_state, "key": key,
                        "epoch": np.zeros((), np.int64)}
                st = restore_checkpoint(ck_path, like)
                gen_p, g_state = st["gen_p"], st["g_state"]
                stu_p, s_state = st["stu_p"], st["s_state"]
                key, start_epoch = st["key"], int(st["epoch"])
        # on a client mesh every carry is placed replicated beside the
        # client-sharded stack, so the first chunk compiles for the
        # placement its outputs keep and later chunks reuse that program
        gen_p, g_state, stu_p, s_state, key = put_replicated(
            (gen_p, g_state, stu_p, s_state, key), mesh)

    def save_ckpt(gp, gs, sp, ss, epoch_done):
        with obs.span("dense.checkpoint"):
            save_checkpoint(ck_path,
                            {"gen_p": gp, "g_state": gs, "stu_p": sp,
                             "s_state": ss, "key": key,
                             "epoch": np.asarray(epoch_done, np.int64)},
                            meta={"epoch": int(epoch_done),
                                  "epochs": int(scfg.epochs)})

    hist = DenseHistory()
    s_steps = getattr(scfg, "s_steps", 1)
    loop_mode = resolve_exec_policy(scfg).loop
    loop_chunk = max(1, int(getattr(scfg, "loop_chunk", 8)))
    poison = frozenset(_poison_epochs or ())
    # both drivers consume the SAME per-epoch key stream so they are
    # interchangeable (and testable against each other); the stream
    # depends only on the (restored) base key, never on start_epoch
    epoch_keys = jax.random.split(key, scfg.epochs)

    def maybe_eval(epoch_done):
        if eval_fn is not None and eval_every and \
                epoch_done % eval_every == 0:
            with obs.span("dense.eval"):
                hist.acc.append((epoch_done, eval_fn(stu_p, student_spec)))

    def check_finite(gl, dl, where):
        bad = not (np.all(np.isfinite(gl)) and np.all(np.isfinite(dl)))
        if bad and nan_policy == "raise":
            raise FloatingPointError(
                f"non-finite loss at {where} (gen={gl}, dis={dl}); "
                "set scfg.nan_policy='skip' or 'rollback' to self-heal")
        return bad

    if loop_mode == "fused":
        snap = None
        for lo, hi in _chunk_bounds(scfg.epochs, loop_chunk, eval_every,
                                    ck_every if ckpt_on else 0,
                                    start_epoch):
            with obs.span("dense.chunk", lo=lo, hi=hi):
                if nan_policy == "rollback":
                    # epochs_step donates its carries — snapshot copies
                    snap = jax.tree.map(jnp.copy,
                                        (gen_p, g_state, stu_p, s_state))
                args = (gen_p, g_state, stu_p, s_state, gparams,
                        epoch_keys[lo:hi])
                obs.keep_program("dense.epochs_step", epochs_step, *args)
                with obs.span("dense.dispatch"):
                    gen_p, g_state, stu_p, s_state, metrics = epochs_step(
                        *args)
                with obs.span("dense.sync"):
                    m = jax.device_get(metrics)  # ONE host sync per chunk
                obs.count("dense.host_syncs")
                with obs.span("dense.history"):
                    hist.gen_loss.extend(float(v) for v in m["gen_loss"])
                    hist.dis_loss.extend(float(v) for v in m["dis_loss"])
                    hist.gen_parts.extend(
                        {k: float(v[i]) for k, v in m["parts"].items()}
                        for i in range(hi - lo))
                    bad = check_finite(m["gen_loss"], m["dis_loss"],
                                       f"epochs [{lo}, {hi})")
                obs.count("dense.chunks")
                obs.count("dense.epochs", hi - lo)
                if bad and nan_policy == "rollback":
                    obs.count("dense.rollbacks")
                    gen_p, g_state, stu_p, s_state = snap
                maybe_eval(hi)
                if _stop_after_epoch and hi >= _stop_after_epoch:
                    return stu_p, gen_p, hist  # simulated kill beats save
                if ckpt_on and hi % ck_every == 0:
                    save_ckpt(gen_p, g_state, stu_p, s_state, hi)
    elif loop_mode == "python":
        b, nz = scfg.synth_batch, scfg.nz
        snap = (gen_p, g_state, stu_p, s_state)
        for epoch in range(start_epoch, scfg.epochs):
            with obs.span("dense.epoch", epoch=epoch):
                # identical derivation to _epoch_body
                kz, ky, ks = jax.random.split(epoch_keys[epoch], 3)
                z = jax.random.normal(kz, (b, nz))
                if epoch in poison:
                    z = jnp.full_like(z, jnp.nan)
                y = jax.random.randint(ky, (b,), 0, scfg.num_classes)
                for _ in range(scfg.t_g):
                    gen_p, g_state, gl, parts = gen_step(
                        gen_p, g_state, stu_p, gparams, z, y)
                stu_p, s_state, dl = student_step(stu_p, s_state, gen_p,
                                                  gparams, z)
                if s_steps > 1:
                    extra = jax.random.normal(ks, (s_steps - 1, b, nz))
                    for j in range(s_steps - 1):
                        stu_p, s_state, dl = student_step(
                            stu_p, s_state, gen_p, gparams, extra[j])
                hist.gen_loss.append(float(gl))
                hist.gen_parts.append({k: float(v) for k, v in parts.items()})
                hist.dis_loss.append(float(dl))
                # one transfer per fetched scalar: both losses and the parts
                obs.count("dense.host_syncs", 2 + len(parts))
                obs.count("dense.epochs")
                bad = check_finite(hist.gen_loss[-1], hist.dis_loss[-1],
                                   f"epoch {epoch}")
                if nan_policy == "rollback":
                    if bad:
                        obs.count("dense.rollbacks")
                        gen_p, g_state, stu_p, s_state = snap
                    else:
                        snap = (gen_p, g_state, stu_p, s_state)
                maybe_eval(epoch + 1)
                if _stop_after_epoch and epoch + 1 >= _stop_after_epoch:
                    return stu_p, gen_p, hist  # simulated kill beats save
                if ckpt_on and (epoch + 1) % ck_every == 0:
                    save_ckpt(gen_p, g_state, stu_p, s_state, epoch + 1)
    else:
        raise ValueError(f"unknown loop_mode {loop_mode!r} "
                         "(expected 'python' or 'fused')")
    return stu_p, gen_p, hist


@functools.partial(jax.jit, static_argnames=("spec",))
def _eval_correct(params, spec: CNNSpec, xb, yb, mask):
    """Scan over pre-batched (nb, B, ...) eval data; returns the total
    correct count as a device scalar (no per-batch host sync)."""
    def body(tot, inp):
        xi, yi, mi = inp
        logits = cnn_logits(params, spec, xi)
        hit = (jnp.argmax(logits, -1) == yi) & mi
        return tot + jnp.sum(hit.astype(jnp.int32)), None

    tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.int32), (xb, yb, mask))
    return tot


def evaluate(params, spec: CNNSpec, x: np.ndarray, y: np.ndarray,
             batch: int = 512, device_batches: int = 64) -> float:
    """Top-1 accuracy, eval-mode BN.

    Batches are padded to a rectangle and reduced with a jit-scanned
    program per device chunk of `device_batches` batches; per-chunk
    correct counts stay on device and the host syncs ONCE at the end —
    versus one sync per batch before. Chunking keeps device memory
    bounded at batch*device_batches rows for arbitrarily large eval
    sets."""
    x, y = np.asarray(x), np.asarray(y)
    n = len(y)
    batch = max(1, min(batch, n))
    nb = -(-n // batch)
    pad = nb * batch - n
    if pad:
        x = np.concatenate([x, np.zeros((pad, *x.shape[1:]), x.dtype)])
        y = np.concatenate([y, np.zeros((pad,), y.dtype)])
    mask = (np.arange(nb * batch) < n).reshape(nb, batch)
    xb = x.reshape(nb, batch, *x.shape[1:])
    yb = y.reshape(nb, batch)
    totals = []
    for i in range(0, nb, device_batches):
        totals.append(_eval_correct(params, spec,
                                    jnp.asarray(xb[i:i + device_batches]),
                                    jnp.asarray(yb[i:i + device_batches]),
                                    jnp.asarray(mask[i:i + device_batches])))
    return int(sum(totals)) / n
