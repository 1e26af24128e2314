#!/usr/bin/env python3
"""Smoke run of the DENSE one-shot round (paper §3, Algorithm 1) on a TPU.

One process drives the round through the entry points a user calls:
``fl.build_federation`` -> ``fl.fedavg`` -> ``core.train_dense_server``
-> ``core.evaluate``, under the execution policy
``configs.backend.resolve_exec_policy`` resolves for ``tpu``, at the
paper's shapes (``configs.paper_cifar.CONFIG``): five ResNet-18 clients
and a ResNet-18 student at width 1.0, 32x32x3 procedural images, local
batch 128, Dirichlet alpha=0.5, synthetic batch 128, nz=100, T_G=30.
Only the epoch counts are cut: 2 local epochs and 16 distillation epochs
(two fused chunks of ``loop_chunk=8``).

    python3 chip_smoke.py             # one chip: the whole round
    python3 chip_smoke.py --chips 4   # four chips: client-sharded round
                                      # against the same round unsharded

JAX is pinned to the TPU before it starts: with no TPU visible the
script exits non-zero and prints no result line. The numbers it prints
are smoke readings, not benchmark results. The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# what resolve_exec_policy must pick on the chip: a stray REPRO_BACKEND
# or REPRO_INTERPRET fails the run instead of running the interpreter
EXPECTED_POLICY = {"backend": "tpu", "interpret": False, "loop": "fused",
                   "distill_kl": "fused"}
LOCAL_EPOCHS = 2
EPOCHS = 16
LOOP_CHUNK = 8
SHARDED_CLIENTS = 8
TEACHER_ATOL = 1e-5
# sharded vs unsharded local training, per client: relative L2 distance
# of the trained params. Float order differs between the two programs
# and ResNet-18 training amplifies the difference; a client trained on
# the wrong shard or put back in the wrong slot is O(1) away.
DRIFT_RTOL = 0.1
# chip vs plain reference on one synthetic batch, in units of the
# reference's largest magnitude
REF_RTOL = 1e-4


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def smoke_config(n_clients: int):
    """The paper's configuration with only the epoch counts cut."""
    from repro.configs.paper_cifar import CONFIG
    return dataclasses.replace(
        CONFIG, n_clients=n_clients,
        client_kinds=(CONFIG.global_kind,) * n_clients,
        local_epochs=LOCAL_EPOCHS, epochs=EPOCHS, loop_chunk=LOOP_CHUNK)


def make_data(scfg, seed: int):
    from repro.data import make_classification_data
    return make_classification_data(
        seed, num_classes=scfg.num_classes, size=scfg.image_size,
        ch=scfg.in_ch, train_per_class=scfg.train_per_class,
        test_per_class=scfg.test_per_class)


def student_spec(scfg):
    from repro.models.cnn import CNNSpec
    return CNNSpec(kind=scfg.global_kind, num_classes=scfg.num_classes,
                   in_ch=scfg.in_ch, width=scfg.width,
                   image_size=scfg.image_size)


def synthetic_batch(scfg, seed: int):
    """One generator batch from a fresh generator: the images stage 2
    feeds the teacher."""
    import jax
    from repro.core import generator as G
    gen = G.img_generator_init(jax.random.PRNGKey(seed), nz=scfg.nz,
                               img_size=scfg.image_size, out_ch=scfg.in_ch)
    z = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (scfg.synth_batch, scfg.nz))
    return G.img_generator(gen, z, img_size=scfg.image_size)


def max_rel_err(got, want) -> float:
    """max |got - want|, in units of max |want| (at least 1)."""
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    return float(np.max(np.abs(got - want)) / max(1.0, np.abs(want).max()))


def check_against_reference(clients, scfg, pol, seed: int) -> None:
    """The chip's stage-2 building blocks against the repo's plain
    references on one synthetic batch: the grouped (im2col) teacher
    against the unrolled per-client ensemble, both at full f32 matmul
    precision, and the fused distill_kl pair (value and student
    gradient) against the materialized log-softmax KL."""
    import jax
    import numpy as np
    from repro.core import (ensemble_logits, grouped_ensemble_logits,
                            softmax_kl, split_clients, stack_grouped)
    from repro.kernels import ref
    from repro.models.cnn import cnn_apply, cnn_init

    x = synthetic_batch(scfg, seed)
    gspecs, gparams = stack_grouped(clients)
    specs, plist = split_clients(clients)
    with jax.default_matmul_precision("highest"):
        teacher = jax.jit(lambda p, xb: grouped_ensemble_logits(
            gspecs, p, xb))(gparams, x)
        want = jax.jit(lambda p, xb: ensemble_logits(specs, p, xb))(plist, x)
    err = max_rel_err(teacher, want)
    print(f"reference: grouped teacher {tuple(teacher.shape)} vs unrolled "
          f"ensemble: max rel err {err:.3e} (limit {REF_RTOL:g})",
          flush=True)
    check(bool(np.all(np.isfinite(np.asarray(teacher)))),
          "non-finite teacher logits")
    check(err <= REF_RTOL, "the grouped teacher disagrees with the "
          "unrolled ensemble")

    spec = student_spec(scfg)
    student, _, _ = cnn_apply(cnn_init(jax.random.PRNGKey(seed + 2), spec),
                              spec, x, train=False)

    def fused(s):
        return softmax_kl(teacher, s, mode="fused", policy=pol).sum()

    def plain(s):
        return ref.distill_kl(teacher, s).sum()

    kl, g = jax.jit(jax.value_and_grad(fused))(student)
    kl_ref, g_ref = jax.jit(jax.value_and_grad(plain))(student)
    errs = (max_rel_err(kl, kl_ref), max_rel_err(g, g_ref))
    print(f"reference: fused distill_kl {tuple(student.shape)}: value "
          f"{float(kl):.6f} vs {float(kl_ref):.6f}, max rel err value "
          f"{errs[0]:.3e} grad {errs[1]:.3e} (limit {REF_RTOL:g})",
          flush=True)
    check(max(errs) <= REF_RTOL,
          "the fused distill_kl kernel disagrees with the reference")


def stage2_program(clients, scfg, key):
    """The fused epoch step ``train_dense_server`` runs, and the
    arguments of one ``loop_chunk``-epoch call, built the same way."""
    import jax
    from repro.core import dense, generator as G
    from repro.models.cnn import cnn_init
    spec = student_spec(scfg)
    k_gen, k_stu, key = jax.random.split(key, 3)
    gen_p = G.img_generator_init(k_gen, nz=scfg.nz,
                                 img_size=scfg.image_size, out_ch=scfg.in_ch)
    stu_p = cnn_init(k_stu, spec)
    (_, _, g_opt, s_opt, gparams, _, epochs_step) = dense.make_dense_steps(
        clients, spec, scfg)
    keys = jax.random.split(key, scfg.epochs)[:scfg.loop_chunk]
    return epochs_step, (gen_p, g_opt.init(gen_p), stu_p, s_opt.init(stu_p),
                         gparams, keys)


def client_rel_dist(a, b):
    """||a_k - b_k|| / ||b_k|| for each client k, over two lists of
    stacked (client-leading) leaves, in float64."""
    import numpy as np

    def sq(tree):
        return sum(np.sum(np.square(t).reshape(len(t), -1), axis=1)
                   for t in tree)
    a = [np.asarray(x, np.float64) for x in a]
    b = [np.asarray(y, np.float64) for y in b]
    return np.sqrt(sq([x - y for x, y in zip(a, b)]) / sq(b))


def check_stage2_program(clients, scfg, key) -> float:
    """Compile the stage-2 chunk program and assert the fused distill_kl
    kernel is in it; returns the compile seconds."""
    step, args = stage2_program(clients, scfg, key)
    t0 = time.perf_counter()
    text = step.lower(*args).compile().as_text()
    secs = time.perf_counter() - t0
    check("tpu_custom_call" in text,
          "no tpu_custom_call in the compiled stage-2 program: the Pallas "
          "distill_kl kernel is not on the chip path")
    return secs


def one_chip(seed: int) -> None:
    import jax
    import numpy as np
    from repro.configs.backend import resolve_exec_policy
    from repro.core import evaluate, train_dense_server
    from repro.fl import build_federation, fedavg

    scfg = smoke_config(5)
    pol = resolve_exec_policy(scfg)
    print(f"policy: backend={pol.backend} interpret={pol.interpret} "
          f"loop={pol.loop} distill_kl={pol.distill_kl} "
          f"kernel_vjp={pol.kernel_vjp} client_loop={pol.client_loop} "
          f"ensemble_shard={pol.ensemble_shard} "
          f"distill_kl_blocks={pol.blocks_for('distill_kl')}", flush=True)
    got = {k: getattr(pol, k) for k in EXPECTED_POLICY}
    check(got == EXPECTED_POLICY,
          f"resolved policy {got} is not the TPU profile {EXPECTED_POLICY}")
    print(f"config: {scfg.n_clients} x {scfg.global_kind} width={scfg.width} "
          f"image={scfg.image_size}x{scfg.image_size}x{scfg.in_ch} "
          f"batch={scfg.batch_size} alpha={scfg.alpha} "
          f"synth_batch={scfg.synth_batch} nz={scfg.nz} t_g={scfg.t_g} "
          f"local_epochs={scfg.local_epochs} epochs={scfg.epochs} "
          f"loop_chunk={scfg.loop_chunk}", flush=True)

    t0 = time.perf_counter()
    data = make_data(scfg, seed)
    xt, yt = data["test"]
    print(f"data: {len(data['train'][1])} train / {len(yt)} test images, "
          f"{time.perf_counter() - t0:.2f} s", flush=True)

    t0 = time.perf_counter()
    clients, _ = build_federation(jax.random.PRNGKey(seed), scfg, data)
    jax.block_until_ready(clients.grouped[1])
    print(f"local training (compile included): "
          f"{time.perf_counter() - t0:.2f} s", flush=True)
    for i, c in enumerate(clients):
        acc = evaluate(c.params, c.spec, xt, yt)
        check(0.0 <= acc <= 1.0, f"client {i} accuracy {acc} out of range")
        print(f"  client{i}: n={c.n_data} local acc={acc:.4f}", flush=True)
    acc_avg = evaluate(fedavg(clients), clients[0].spec, xt, yt)
    print(f"one-shot FedAvg acc: {acc_avg:.4f}", flush=True)
    check_against_reference(clients, scfg, pol, seed + 2)

    key = jax.random.PRNGKey(seed + 1)
    compile_s = check_stage2_program(clients, scfg, key)
    print(f"stage-2 chunk program compiled in {compile_s:.2f} s; "
          "tpu_custom_call present", flush=True)

    marks = []

    def eval_fn(params, spec):
        # called after each fused chunk's host sync
        marks.append(time.perf_counter())
        acc = evaluate(params, spec, xt, yt)
        marks.append(time.perf_counter())
        return acc

    t0 = time.perf_counter()
    _, _, hist = train_dense_server(key, clients, scfg, eval_fn=eval_fn,
                                    eval_every=scfg.loop_chunk)
    check(len(hist.gen_loss) == scfg.epochs == len(hist.dis_loss),
          f"expected {scfg.epochs} epochs of losses, got "
          f"{len(hist.gen_loss)}/{len(hist.dis_loss)}")
    for e, (gl, dl, parts) in enumerate(zip(hist.gen_loss, hist.dis_loss,
                                            hist.gen_parts)):
        print(f"  epoch {e:2d}: gen={gl:.5f} (ce={parts['ce']:.5f} "
              f"bn={parts['bn']:.5f} div={parts['div']:.5f}) "
              f"student={dl:.5f}", flush=True)
    losses = np.asarray(hist.gen_loss + hist.dis_loss)
    check(bool(np.all(np.isfinite(losses))), "non-finite stage-2 loss")
    check(len(marks) == 4, f"expected 2 chunk evaluations, got {len(marks)}")
    print(f"stage 2: first chunk (set-up + compile) {marks[0] - t0:.2f} s, "
          f"second chunk (compiled) {marks[2] - marks[1]:.2f} s", flush=True)
    for epoch, acc in hist.acc:
        print(f"  student acc after epoch {epoch}: {acc:.4f}", flush=True)
    stu_acc = hist.acc[-1][1]
    check(0.0 <= stu_acc <= 1.0, f"student accuracy {stu_acc} out of range")
    print(f"DENSE student acc: {stu_acc:.4f}", flush=True)


def four_chips(seed: int) -> None:
    import jax
    import numpy as np
    from repro.core import grouped_ensemble_logits, stack_grouped
    from repro.fl import build_federation, put_grouped, resolve_mesh

    n_dev = len(jax.devices())
    check(n_dev == 4, f"--chips 4 needs 4 devices, JAX sees {n_dev}")
    scfg = smoke_config(SHARDED_CLIENTS)
    sharded = dataclasses.replace(scfg, ensemble_shard_mode="clients")
    mesh = resolve_mesh(sharded)
    print(f"mesh: axes={dict(mesh.shape)} devices="
          f"{[d.id for d in mesh.devices.flat]}", flush=True)
    check(mesh.devices.size == 4 and len(set(mesh.devices.flat)) == 4,
          "the client mesh does not span 4 distinct devices")
    data = make_data(scfg, seed)
    key = jax.random.PRNGKey(seed)

    t0 = time.perf_counter()
    ref, _ = build_federation(key, scfg, data)
    jax.block_until_ready(ref.grouped[1])
    print(f"unsharded local training ({scfg.n_clients} clients, one "
          f"device): {time.perf_counter() - t0:.2f} s", flush=True)
    t0 = time.perf_counter()
    shd, _ = build_federation(key, sharded, data)
    jax.block_until_ready(shd.grouped[1])
    print(f"sharded local training ({scfg.n_clients} clients over "
          f"{mesh.devices.size} devices): {time.perf_counter() - t0:.2f} s",
          flush=True)

    leaf = jax.tree.leaves(shd.grouped[1][0])[0]
    placed = sorted({s.device.id for s in leaf.addressable_shards})
    rows = sorted(s.index[0].start for s in leaf.addressable_shards)
    print(f"sharded params: leading client dim on devices {placed}, "
          f"shard row offsets {rows}", flush=True)
    check(len(placed) == 4, f"sharded params sit on devices {placed}")

    a = jax.tree.leaves(ref.grouped[1])
    b = jax.tree.leaves(shd.grouped[1])
    n_exact = sum(np.array_equal(np.asarray(x), np.asarray(y))
                  for x, y in zip(a, b))
    diff = max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
               for x, y in zip(a, b))
    drift = client_rel_dist(b, a)
    other = client_rel_dist([np.roll(np.asarray(x), 1, axis=0) for x in a], a)
    print(f"params after local training: {n_exact}/{len(a)} leaves "
          f"bitwise equal, max abs diff {diff:.3e}; per-client relative L2 "
          f"distance max {drift.max():.3e} (limit {DRIFT_RTOL:g}), to "
          f"another client min {other.min():.3e}", flush=True)

    # the teacher alone, on the same (unsharded) params in both
    # placements, at full f32 matmul precision
    x = synthetic_batch(scfg, seed + 2)
    gspecs, ref_p = stack_grouped(ref)
    shd_p = put_grouped(gspecs, ref_p, mesh)
    with jax.default_matmul_precision("highest"):
        ref_lg = jax.jit(lambda p, xb: grouped_ensemble_logits(
            gspecs, p, xb))(ref_p, x)
        shd_lg = jax.jit(lambda p, xb: grouped_ensemble_logits(
            gspecs, p, xb, mesh=mesh))(shd_p, x)
    err = float(np.max(np.abs(np.asarray(ref_lg) - np.asarray(shd_lg))))
    print(f"teacher logits {tuple(ref_lg.shape)}, same params: max abs diff "
          f"{err:.3e} (limit {TEACHER_ATOL:g})", flush=True)
    check(bool(np.all(np.isfinite(np.asarray(shd_lg)))),
          "non-finite sharded teacher logits")
    # both comparisons are printed before either can fail the run
    check(drift.max() <= DRIFT_RTOL, "sharded local training drifted from "
          "the unsharded round by more than float order explains")
    check(err <= TEACHER_ATOL, "sharded teacher logits differ from the "
          "unsharded teacher")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the whole round on one chip; 4: only the "
                         "client-sharded round and its unsharded reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # pin JAX to the TPU before it starts: no quiet fallback to the CPU
    os.environ["JAX_PLATFORMS"] = "tpu"
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no TPU visible to JAX ({e})", file=sys.stderr)
        return 1
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX runs on {dev.platform!r}, not a TPU",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    warm = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"devices: {len(devices)} x {dev.device_kind} ({dev.platform}); "
          f"jax {jax.__version__}; compile cache {cache} "
          f"({warm} entries at start)", flush=True)

    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips(args.seed)
        else:
            one_chip(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"peak_bytes_in_use (device 0): {peak} "
          f"({peak / 2**30:.3f} GiB)" if peak is not None else
          "peak_bytes_in_use: not reported by the backend", flush=True)
    print(f"total: {time.perf_counter() - t0:.2f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
