"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per the repo contract. Default is
a CI-sized budget; ``--full`` uses the budget behind EXPERIMENTS.md.

  T1  accuracy across alpha (non-IID severity) x methods     [Table 1]
  T2  heterogeneous client architectures                     [Table 2]
  T3  accuracy vs number of clients                          [Table 3]
  T4  DENSE + LDAM on skewed data                            [Table 4]
  T5  multi-round extension                                  [Table 5]
  T6  generator-loss ablation (CE / BN / div)                [Table 6]
  F3  one-shot FedAvg vs DENSE vs local models               [Figure 3]
  K   kernel microbenches (vs jnp oracle on CPU)             [kernels/]
  KL  distill-KL fwd / fwd+bwd, ref vs fused custom-VJP      [§Perf]
  ATTN flash-attention fwd / fwd+bwd, ref vs fused VJP pair  [§Perf]
  SSD  ssd chunked scan fwd / fwd+bwd, ref vs fused VJP pair [§Perf]
  E   ensemble forward looped vs grouped-vmap; epochs/sec    [§Perf]
  C   client local training looped vs grouped engine         [§Perf]
  S   client-axis mesh sharding vs single-device grouped     [§Perf]
  R   robustness: accuracy + clients/sec vs dropout_frac,
      quarantine admission, checkpoint/resume overhead       [§Robust]
  BK  backend execution-policy registry: registry-default vs
      autotuned blocks per kernel pair, resolution overhead  [§Perf]
  SERVE continuous-batching ServeEngine, paged vs dense, under
      a seeded Poisson arrival trace: tok/s + p50/p99        [§Serving]
  ROOF roofline summary from dry-run artifacts               [§Roofline]

``--json PATH`` additionally writes every emitted record plus per-table
medians as one machine-readable document (the BENCH_PR9.json perf
trajectory artifact; scripts/tier1.sh writes it, CI uploads it and
benchmarks/check_regression.py gates PRs on the per-series medians).
"""
from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (base_cfg, emit, ensemble_acc, get_federation,
                               run_method, time_ab, time_call)


def t1_alpha_sweep(full: bool):
    alphas = (0.1, 0.3, 0.5) if full else (0.1, 0.5)
    methods = ("fedavg", "feddf", "feddafl", "fedadi", "dense")
    for alpha in alphas:
        scfg = dataclasses.replace(base_cfg(full), alpha=alpha)
        ens = ensemble_acc(scfg)
        emit(f"t1/ensemble_ceiling/alpha{alpha}", 0.0, f"acc={ens:.4f}")
        for m in methods:
            acc, dt = run_method(m, scfg)
            emit(f"t1/{m}/alpha{alpha}", dt, f"acc={acc:.4f}")


def t2_heterogeneous(full: bool):
    kinds = (("resnet18", "cnn1", "cnn2", "wrn16_1", "wrn40_1") if full
             else ("cnn1", "cnn2", "wrn16_1"))
    scfg = dataclasses.replace(
        base_cfg(full), client_kinds=kinds, n_clients=len(kinds),
        global_kind="wrn16_1" if not full else "resnet18")
    for m in ("feddf", "feddafl", "fedadi", "dense"):
        acc, dt = run_method(m, scfg)
        emit(f"t2/{m}/hetero{len(kinds)}", dt, f"acc={acc:.4f}")


def t3_num_clients(full: bool):
    ms = (5, 10, 20) if full else (3, 6)
    for n in ms:
        scfg = dataclasses.replace(base_cfg(full), n_clients=n,
                                   client_kinds=("cnn1",) * n)
        for m in (("fedavg", "feddf", "fedadi", "dense") if full
                  else ("fedavg", "dense")):
            acc, dt = run_method(m, scfg)
            emit(f"t3/{m}/m{n}", dt, f"acc={acc:.4f}")


def t4_ldam(full: bool):
    for alpha in ((0.1, 0.5) if full else (0.1,)):
        for ldam in (False, True):
            scfg = dataclasses.replace(base_cfg(full), alpha=alpha,
                                       use_ldam=ldam)
            acc, dt = run_method("dense", scfg)
            name = "dense+ldam" if ldam else "dense"
            emit(f"t4/{name}/alpha{alpha}", dt, f"acc={acc:.4f}")


def t5_multiround(full: bool):
    from repro.core import evaluate
    from repro.data import make_classification_data
    from repro.fl import dense_multi_round
    rounds = (1, 2, 3) if full else (1, 2)
    scfg = dataclasses.replace(base_cfg(full),
                               local_epochs=8 if full else 4)
    data = make_classification_data(0, num_classes=scfg.num_classes,
                                    size=scfg.image_size, ch=scfg.in_ch,
                                    train_per_class=scfg.train_per_class,
                                    test_per_class=scfg.test_per_class)
    xt, yt = data["test"]
    for tc in rounds:
        t0 = time.time()
        gp, spec, _ = dense_multi_round(jax.random.PRNGKey(0), scfg, data,
                                        rounds=tc)
        acc = evaluate(gp, spec, xt, yt)
        emit(f"t5/dense/rounds{tc}", time.time() - t0, f"acc={acc:.4f}")


def t6_ablation(full: bool):
    from repro.core import evaluate, train_dense_server
    scfg = base_cfg(full)
    data, clients, _ = get_federation(scfg)
    xt, yt = data["test"]
    variants = {"dense": {}, "w_ce_only": {"use_bn": False, "use_div": False},
                "wo_bn": {"use_bn": False}, "wo_div": {"use_div": False}}
    for name, kw in variants.items():
        t0 = time.time()
        stu, _, _ = train_dense_server(jax.random.PRNGKey(7), clients, scfg,
                                       **kw)
        acc = evaluate(stu, clients[0].spec, xt, yt)
        emit(f"t6/{name}", time.time() - t0, f"acc={acc:.4f}")


def f3_local_vs_global(full: bool):
    """Figure 3: DENSE above local models; one-shot FedAvg below them."""
    from repro.core import evaluate
    scfg = base_cfg(full)
    data, clients, _ = get_federation(scfg)
    xt, yt = data["test"]
    for i, c in enumerate(clients):
        acc = evaluate(c.params, c.spec, xt, yt)
        emit(f"f3/local{i}", 0.0, f"acc={acc:.4f}")
    for m in ("fedavg", "dense"):
        acc, dt = run_method(m, scfg)
        emit(f"f3/{m}", dt, f"acc={acc:.4f}")


def k_kernels(full: bool):
    """Kernel microbenches. time_call = warmup + median-of-N, so the
    reported µs is steady-state runtime, not compile time. Block shapes
    are pinned as explicit ExecPolicy overrides (configs/backend.py) so
    the series stays comparable across autotune-cache changes;
    kernel_vjp="autodiff" runs the bare forward kernels."""
    from repro.configs.backend import resolve_exec_policy
    from repro.kernels import ops, ref
    pol = resolve_exec_policy(None).replace(kernel_vjp="autodiff")
    key = jax.random.PRNGKey(0)
    B, Hq, Hkv, S, D = 1, 4, 2, 256, 64
    q = jax.random.normal(key, (B, Hq, S, D))
    k = jax.random.normal(key, (B, Hkv, S, D))
    v = jax.random.normal(key, (B, Hkv, S, D))
    p_fa = pol.override_blocks("flash_attention", block_q=64, block_k=64)
    dt = time_call(lambda: ops.flash_attention(q, k, v, policy=p_fa))
    o = ops.flash_attention(q, k, v, policy=p_fa)
    err = float(jnp.max(jnp.abs(o - ref.attention(q, k, v))))
    emit("k/flash_attention/256x64", dt, f"max_err={err:.2e};interpret=cpu")

    t_ = jax.random.normal(key, (64, 4096)) * 3
    s_ = jax.random.normal(jax.random.PRNGKey(1), (64, 4096)) * 3
    p_kl = pol.override_blocks("distill_kl", block_rows=32, block_v=1024)
    dt = time_call(lambda: ops.distill_kl(t_, s_, policy=p_kl))
    r = ops.distill_kl(t_, s_, policy=p_kl)
    err = float(jnp.max(jnp.abs(r - ref.distill_kl(t_, s_))))
    emit("k/distill_kl/64x4096", dt, f"max_err={err:.2e};interpret=cpu")

    x = jax.random.normal(key, (1, 256, 4, 32))
    dt_in = jax.nn.softplus(jax.random.normal(key, (1, 256, 4)))
    a = -jnp.exp(jax.random.normal(key, (4,)) * 0.3)
    b = jax.random.normal(key, (1, 256, 1, 32)) * 0.3
    c = jax.random.normal(key, (1, 256, 1, 32)) * 0.3
    p_ssd = pol.override_blocks("ssd_scan", chunk=64)
    dt = time_call(lambda: ops.ssd_scan(x, dt_in, a, b, c, policy=p_ssd))
    y, _ = ops.ssd_scan(x, dt_in, a, b, c, policy=p_ssd)
    y2, _ = ref.ssd(x, dt_in, a, b, c)
    err = float(jnp.max(jnp.abs(y - y2)))
    emit("k/ssd_scan/256x4x32", dt, f"max_err={err:.2e};interpret=cpu")


def kl_distill(full: bool):
    """KL: the stage-2 distillation loss, forward and forward+backward,
    ref (materialized jnp autodiff) vs the fused custom-VJP Pallas pair
    (kernels/distill_kl, DESIGN.md §9). On this CPU host the kernels run
    in interpret mode, so the µs columns measure the interpreter, not the
    Mosaic lowering — the trackable claims are the grad-equivalence error
    and the analytic peak-HBM residual bytes, which are backend-free."""
    from repro.configs.backend import resolve_exec_policy
    from repro.kernels import ops, ref
    R, V = 64, 4096
    br, bv = 32, 1024
    pol = resolve_exec_policy(None).override_blocks(
        "distill_kl", block_rows=br, block_v=bv)
    t = jax.random.normal(jax.random.PRNGKey(0), (R, V)) * 3
    s = jax.random.normal(jax.random.PRNGKey(1), (R, V)) * 3
    g = jnp.ones((R,), jnp.float32) / R
    iters = 5 if full else 3

    f_ref = jax.jit(ref.distill_kl)
    f_fus = jax.jit(lambda a, b: ops.distill_kl(a, b, policy=pol))

    def fwdbwd(fwd):
        def run(a, b):
            out, pull = jax.vjp(fwd, a, b)
            return out, pull(g)
        return jax.jit(run)

    fb_ref = fwdbwd(ref.distill_kl)
    fb_fus = fwdbwd(lambda a, b: ops.distill_kl(a, b, policy=pol))

    err_f = float(jnp.max(jnp.abs(f_fus(t, s) - f_ref(t, s))))
    (_, (dt_r, ds_r)), (_, (dt_k, ds_k)) = fb_ref(t, s), fb_fus(t, s)
    err_b = max(float(jnp.max(jnp.abs(dt_k - dt_r))),
                float(jnp.max(jnp.abs(ds_k - ds_r))))

    shape = f"{R}x{V}"
    for name, fn in (("fwd/ref", f_ref), ("fwd/fused", f_fus),
                     ("fwdbwd/ref", fb_ref), ("fwdbwd/fused", fb_fus)):
        dt = time_call(fn, t, s, warmup=1, iters=iters)
        err = err_f if name.startswith("fwd/") else err_b
        emit(f"kl/{name}/{shape}", dt, f"max_err={err:.2e};interpret=cpu")

    # analytic residual bytes saved fwd->bwd (what HBM must hold between
    # the passes): ref keeps two (R, V) f32 log-softmaxes; fused folds
    # its five online accumulators into three f32 rows — lse_t, lse_s,
    # kl (distill_kl._vjp_fwd; inputs are alive in both cases)
    def residuals(r, v):
        return 2 * 4 * r * v, 3 * 4 * r
    rb, fb = residuals(R, V)
    rb_p, fb_p = residuals(4096, 262144)
    emit(f"kl/residual_bytes/{shape}", 0.0,
         (f"ref={rb};fused={fb};ratio={rb / fb:.0f}x;"
          f"paper_scale_4096x262144:ref={rb_p};fused={fb_p}"))


def attn_flash(full: bool):
    """ATTN: blockwise attention forward and forward+backward, ref
    (materialized XLA softmax + autodiff) vs the streaming custom-VJP
    Pallas pair (kernels/flash_attention, DESIGN.md §9). Like the kl
    table, the CPU µs columns measure the interpreter — the trackable
    claims are grad-equivalence error and the analytic fwd→bwd residual
    bytes, which are backend-free."""
    from repro.configs.backend import resolve_exec_policy
    from repro.kernels import ops, ref
    B, Hq, Hkv, S, D = 1, 4, 2, 256, 64
    bq = bk = 64
    pol = resolve_exec_policy(None).replace(
        kernel_vjp="fused").override_blocks(
            "flash_attention", block_q=bq, block_k=bk)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, Hq, S, D))
    k = jax.random.normal(ks[1], (B, Hkv, S, D))
    v = jax.random.normal(ks[2], (B, Hkv, S, D))
    g = jax.random.normal(ks[3], (B, Hq, S, D))
    iters = 5 if full else 3

    f_ref = jax.jit(lambda a, b, c: ref.attention(a, b, c))
    f_fus = jax.jit(lambda a, b, c: ops.flash_attention(a, b, c,
                                                        policy=pol))

    def fwdbwd(fwd):
        def run(a, b, c):
            out, pull = jax.vjp(fwd, a, b, c)
            return out, pull(g)
        return jax.jit(run)

    fb_ref = fwdbwd(lambda a, b, c: ref.attention(a, b, c))
    fb_fus = fwdbwd(lambda a, b, c: ops.flash_attention(a, b, c,
                                                        policy=pol))

    err_f = float(jnp.max(jnp.abs(f_fus(q, k, v) - f_ref(q, k, v))))
    (_, gr), (_, gk) = fb_ref(q, k, v), fb_fus(q, k, v)
    err_b = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(gk, gr))

    shape = f"{S}x{D}"
    for name, fn in (("fwd/ref", f_ref), ("fwd/fused", f_fus),
                     ("fwdbwd/ref", fb_ref), ("fwdbwd/fused", fb_fus)):
        dt = time_call(fn, q, k, v, warmup=1, iters=iters)
        err = err_f if name.startswith("fwd/") else err_b
        emit(f"attn/{name}/{shape}", dt, f"max_err={err:.2e};interpret=cpu")

    # analytic residual bytes fwd->bwd: ref/autodiff keeps the (B,Hq,S,S)
    # f32 probability matrix alive between the passes; the fused pair
    # keeps only the f32 output + per-row lse (flash_attention._vjp_fwd;
    # inputs are alive in both cases)
    def residuals(b_, h_, s_, d_):
        return 4 * b_ * h_ * s_ * s_, 4 * b_ * h_ * s_ * (d_ + 1)
    rb, fb = residuals(B, Hq, S, D)
    rb_p, fb_p = residuals(1, 32, 32768, 128)
    emit(f"attn/residual_bytes/{shape}", 0.0,
         (f"ref={rb};fused={fb};ratio={rb / fb:.0f}x;"
          f"prefill_32k_1x32x32768x128:ref={rb_p};fused={fb_p}"))


def ssd_table(full: bool):
    """SSD: the Mamba-2 chunked scan forward and forward+backward, ref
    (sequential jnp recurrence + autodiff) vs the reversed-recurrence
    custom-VJP Pallas pair (kernels/ssd_scan, DESIGN.md §9). Same CPU
    caveat as attn/kl: µs measures the interpreter; grad error and
    residual bytes are the backend-free claims."""
    from repro.configs.backend import resolve_exec_policy
    from repro.kernels import ops, ref
    B, S, H, P, G, N = 1, 256, 4, 32, 1, 32
    cl = 64
    pol = resolve_exec_policy(None).replace(
        kernel_vjp="fused").override_blocks("ssd_scan", chunk=cl)
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt_in = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    b = jax.random.normal(ks[3], (B, S, G, N)) * 0.3
    c = jax.random.normal(ks[4], (B, S, G, N)) * 0.3
    gy = jax.random.normal(ks[5], (B, S, H, P))
    gs = jax.random.normal(ks[6], (B, H, P, N)) * 0.1
    iters = 5 if full else 3

    f_ref = jax.jit(lambda *ar: ref.ssd(*ar))
    f_fus = jax.jit(lambda *ar: ops.ssd_scan(*ar, policy=pol))

    def fwdbwd(fwd):
        def run(*ar):
            (y, st), pull = jax.vjp(fwd, *ar)
            return y, pull((gy, gs))
        return jax.jit(run)

    fb_ref = fwdbwd(lambda *ar: ref.ssd(*ar))
    fb_fus = fwdbwd(lambda *ar: ops.ssd_scan(*ar, policy=pol))

    args = (x, dt_in, a, b, c)
    (y1, s1), (y2, s2) = f_ref(*args), f_fus(*args)
    err_f = max(float(jnp.max(jnp.abs(y1 - y2))),
                float(jnp.max(jnp.abs(s1 - s2))))
    (_, gr), (_, gk) = fb_ref(*args), fb_fus(*args)
    err_b = max(float(jnp.max(jnp.abs(a_ - b_)))
                for a_, b_ in zip(gk, gr))

    shape = f"{S}x{H}x{P}"
    for name, fn in (("fwd/ref", f_ref), ("fwd/fused", f_fus),
                     ("fwdbwd/ref", fb_ref), ("fwdbwd/fused", fb_fus)):
        dt = time_call(fn, *args, warmup=1, iters=iters)
        err = err_f if name.startswith("fwd/") else err_b
        emit(f"ssd/{name}/{shape}", dt, f"max_err={err:.2e};interpret=cpu")

    # analytic residual bytes fwd->bwd: autodiff of the recurrence keeps
    # the full (B,S,H,P,N) f32 state history; the fused pair keeps one
    # carried state per CHUNK (ssd_scan._vjp_fwd) — ratio = chunk length
    def residuals(b_, s_, h_, p_, n_, cl_):
        return 4 * b_ * s_ * h_ * p_ * n_, \
            4 * b_ * h_ * (-(-s_ // cl_)) * p_ * n_
    rb, fb = residuals(B, S, H, P, N, cl)
    rb_p, fb_p = residuals(1, 32768, 64, 64, 128, 256)
    emit(f"ssd/residual_bytes/{shape}", 0.0,
         (f"ref={rb};fused={fb};ratio={rb / fb:.0f}x;"
          f"prefill_32k_1x32768x64x64x128:ref={rb_p};fused={fb_p}"))


def e_ensemble(full: bool):
    """E: the DENSE server hot paths. (a) ensemble-forward µs/call,
    unrolled loop vs grouped-vmap, m ∈ {5,10,20} homogeneous clients;
    (b) epochs/sec of train_dense_server for loop_mode python vs fused.
    Post-warmup medians (time_call); trained clients are unnecessary —
    random inits have identical cost."""
    from repro.core.ensemble import (Client, ensemble_logits,
                                     grouped_ensemble_logits, split_clients,
                                     stack_grouped)
    from repro.models.cnn import CNNSpec, cnn_init
    spec = CNNSpec(kind="cnn1", num_classes=10, in_ch=3, width=0.5,
                   image_size=16)
    # per-call latency at a serving-style microbatch — the regime where
    # the unrolled loop pays m× fixed conv cost and the fused grouped
    # path (one batched GEMM per layer) structurally wins; large batches
    # are conv-FLOP-bound and converge to the same floor for all paths
    b = 16
    x = jax.random.normal(jax.random.PRNGKey(0), (b, 16, 16, 3))
    for m in (5, 10, 20):
        clients = [Client(spec=spec,
                          params=cnn_init(jax.random.PRNGKey(i), spec))
                   for i in range(m)]
        specs, cparams = split_clients(clients)
        gspecs, gparams = stack_grouped(clients)
        f_loop = jax.jit(lambda cp, xb: ensemble_logits(specs, cp, xb))
        f_grp = jax.jit(
            lambda gp, xb: grouped_ensemble_logits(gspecs, gp, xb))
        t_loop, t_grp = time_ab(f_loop, (cparams, x), f_grp, (gparams, x))
        emit(f"e/ensemble_forward/looped/m{m}", t_loop, f"batch={b}")
        emit(f"e/ensemble_forward/grouped/m{m}", t_grp,
             f"batch={b};speedup={t_loop / t_grp:.2f}x")

    # epochs/sec of the two epoch drivers, steady state. Build the jitted
    # steps ONCE (train_dense_server rebuilds them per call, which would
    # make every timed call recompile and report compile time as runtime)
    # and time repeated passes threading the carry through, so donated
    # buffers stay valid and compile happens only in the warmup pass.
    from repro.core import generator as G
    from repro.core.dense import make_dense_steps
    n = 4
    scfg = dataclasses.replace(
        base_cfg(False), n_clients=n, client_kinds=("cnn1",) * n,
        num_classes=6, image_size=16, width=0.25, nz=16, t_g=2,
        synth_batch=32, s_steps=1, loop_chunk=4)
    cspec = CNNSpec(kind="cnn1", num_classes=scfg.num_classes, in_ch=3,
                    width=scfg.width, image_size=scfg.image_size)
    clients = [Client(spec=cspec,
                      params=cnn_init(jax.random.PRNGKey(i), cspec))
               for i in range(n)]
    (gen_step, student_step, g_opt, s_opt, gparams, _,
     epochs_step) = make_dense_steps(clients, cspec, scfg)
    key = jax.random.PRNGKey(0)
    k_gen, k_stu, key = jax.random.split(key, 3)
    gen_p0 = G.img_generator_init(k_gen, nz=scfg.nz,
                                  img_size=scfg.image_size, out_ch=3)
    stu_p0 = cnn_init(k_stu, cspec)
    keys = jax.random.split(key, scfg.loop_chunk)
    passes = 3 if not full else 8

    def python_pass(state):
        gen_p, g_state, stu_p, s_state = state
        b, nz = scfg.synth_batch, scfg.nz
        for ek in keys:
            kz, ky, _ = jax.random.split(ek, 3)
            z = jax.random.normal(kz, (b, nz))
            yl = jax.random.randint(ky, (b,), 0, scfg.num_classes)
            for _ in range(scfg.t_g):
                gen_p, g_state, gl, _ = gen_step(gen_p, g_state, stu_p,
                                                 gparams, z, yl)
            stu_p, s_state, dl = student_step(stu_p, s_state, gen_p,
                                              gparams, z)
        jax.block_until_ready(dl)
        return gen_p, g_state, stu_p, s_state

    def fused_pass(state):
        out = epochs_step(*state, gparams, keys)
        jax.block_until_ready(out[4]["dis_loss"])
        return out[:4]

    for mode, one_pass in (("python", python_pass), ("fused", fused_pass)):
        # fresh copies per mode: epochs_step donates its carry, which
        # would delete gen_p0/stu_p0 for any later use
        state = jax.tree.map(jnp.copy, (gen_p0, g_opt.init(gen_p0),
                                        stu_p0, s_opt.init(stu_p0)))
        state = one_pass(state)                 # warmup: compile
        ts = []
        for _ in range(passes):
            t0 = time.perf_counter()
            state = one_pass(state)
            ts.append(time.perf_counter() - t0)
        dt = float(np.median(ts))
        emit(f"e/epochs_per_sec/{mode}", dt,
             f"epochs={scfg.loop_chunk};eps={scfg.loop_chunk / dt:.2f}")


def c_client_training(full: bool):
    """C: the federation's local-update phase. Per-client python loop
    (one jitted step per minibatch, host-side slicing) vs the grouped
    engine (fl/federation: one fused scanned program per architecture
    group), m ∈ {5,10,20}, homogeneous cnn1 and 2-group cnn1/cnn2
    heterogeneous. Both sides run the IDENTICAL seeded schedule on
    ragged shards (n=40, batch=16 -> two full + one half batch per
    epoch); time_ab interleaves the passes; the grouped side re-stacks
    inits and rebuilds its batch plan every pass (that host work is part
    of the engine's cost). Sized at the CI-scale client spec the tier-1
    suite trains (image 8, width 0.25) — the per-step-fixed-cost /
    dispatch-dominated regime the grouped engine targets; at
    paper-scale widths on this 1-2-core CPU host both paths are
    conv-FLOP-bound and converge (an accelerator backend changes the
    regime — the backend registry, configs/backend.py, owns that flip). Reported derived values: µs per real
    optimizer step and whole-federation clients/sec."""
    from repro.data.pipeline import batches, build_batch_plan, pad_shards
    from repro.fl.client import make_grouped_local_update, make_local_step
    from repro.fl.federation import group_specs
    from repro.models.cnn import CNNSpec, cnn_init

    n_per, batch, epochs = 40, 16, 2
    steps_per_client = epochs * (-(-n_per // batch))
    rng = np.random.default_rng(0)

    def spec_of(kind):
        return CNNSpec(kind=kind, num_classes=6, in_ch=3, width=0.25,
                       image_size=8)

    for m in (5, 10, 20):
        for variant in ("homog", "hetero2"):
            kinds = ("cnn1",) * m if variant == "homog" else \
                tuple("cnn1" if i % 2 == 0 else "cnn2" for i in range(m))
            specs = [spec_of(k) for k in kinds]
            shards = [(rng.standard_normal((n_per, 8, 8, 3))
                       .astype(np.float32), rng.integers(0, 6, n_per))
                      for _ in range(m)]
            inits = [cnn_init(jax.random.PRNGKey(i), s)
                     for i, s in enumerate(specs)]
            groups = group_specs(specs)
            zeros_marg = jnp.zeros((6,))
            group_data = [(spec, idx, *pad_shards([shards[i] for i in idx]))
                          for spec, idx in groups]

            def looped_pass():
                # block on EVERY client's final loss: with async dispatch,
                # syncing only the last client would stop the clock while
                # earlier clients' chains are still in flight
                done = []
                for spec, idx in groups:
                    step, opt = make_local_step(spec, lr=0.01, momentum=0.9,
                                                use_ldam=False)
                    for i in idx:
                        p, st = inits[i], opt.init(inits[i])
                        for bx, by in batches(*shards[i], batch, seed=i,
                                              epochs=epochs):
                            p, st, loss = step(p, st, jnp.asarray(bx),
                                               jnp.asarray(by), zeros_marg)
                        done.append(loss)
                jax.block_until_ready(done)

            def grouped_pass():
                done = []
                for spec, idx, xs, ys in group_data:
                    run, opt = make_grouped_local_update(
                        spec, lr=0.01, momentum=0.9, use_ldam=False)
                    plan = build_batch_plan([n_per] * len(idx), batch,
                                            epochs=epochs,
                                            seeds=list(idx))
                    stacked0 = jax.tree.map(
                        lambda *a: jnp.stack(a), *[inits[i] for i in idx])
                    p, s, losses = run(stacked0, opt.init(stacked0),
                                       jnp.asarray(xs), jnp.asarray(ys),
                                       jnp.asarray(plan.idx),
                                       jnp.asarray(plan.mask),
                                       jnp.zeros((len(idx), 6)))
                    done.append(losses)
                jax.block_until_ready(done)

            t_loop, t_grp = time_ab(looped_pass, (), grouped_pass, (),
                                    warmup=2, iters=7 if not full else 15)
            total_steps = m * steps_per_client
            for name, t in (("looped", t_loop), ("grouped", t_grp)):
                emit(f"c/local_train/{name}/{variant}/m{m}",
                     t / total_steps,
                     f"clients_per_sec={m / t:.2f};steps={total_steps}")
            emit(f"c/local_train/speedup/{variant}/m{m}", 0.0,
                 f"grouped_over_looped={t_loop / t_grp:.2f}x")


def s_sharding(full: bool):
    """S: the client-axis mesh (fl/sharding). (a) grouped ensemble
    forward, single-device vs sharded-over-("clients","data"); (b) the
    grouped local-update scan, unplaced vs client-sharded placement.
    On a 1-device host the mesh is degenerate (clients axis = 1) and the
    table measures pure shard_map/placement overhead; run under
    XLA_FLAGS=--xla_force_host_platform_device_count=N (or an accelerator
    backend) for real-axis numbers — derived reports the axis size so
    the trajectory records which regime was measured."""
    from repro.core.ensemble import (Client, grouped_ensemble_logits,
                                     stack_grouped)
    from repro.data.pipeline import build_batch_plan, pad_shards
    from repro.fl.client import make_grouped_local_update
    from repro.fl.sharding import (client_axis_size, group_shardable,
                                   put_grouped, put_stacked)
    from repro.launch.mesh import make_client_mesh
    from repro.models.cnn import CNNSpec, cnn_init

    mesh = make_client_mesh()
    c = client_axis_size(mesh)
    spec = CNNSpec(kind="cnn1", num_classes=10, in_ch=3, width=0.5,
                   image_size=16)
    b = 16
    x = jax.random.normal(jax.random.PRNGKey(0), (b, 16, 16, 3))
    for m in (8, 16):
        clients = [Client(spec=spec,
                          params=cnn_init(jax.random.PRNGKey(i), spec))
                   for i in range(m)]
        gspecs, gparams = stack_grouped(clients)
        sharded = group_shardable(mesh, m)
        gp_sh = put_grouped(gspecs, gparams, mesh)
        f_one = jax.jit(lambda gp, xb: grouped_ensemble_logits(gspecs, gp,
                                                               xb))
        f_sh = jax.jit(lambda gp, xb: grouped_ensemble_logits(
            gspecs, gp, xb, mesh=mesh))
        t_one, t_sh = time_ab(f_one, (gparams, x), f_sh, (gp_sh, x))
        emit(f"s/ensemble_forward/single/m{m}", t_one, f"batch={b}")
        emit(f"s/ensemble_forward/sharded/m{m}", t_sh,
             (f"batch={b};clients_axis={c};sharded={sharded};"
              f"speedup={t_one / t_sh:.2f}x"))

    n_per, batch, epochs = 40, 16, 2
    rng = np.random.default_rng(0)
    tspec = CNNSpec(kind="cnn1", num_classes=6, in_ch=3, width=0.25,
                    image_size=8)
    for m in (8, 16):
        shards = [(rng.standard_normal((n_per, 8, 8, 3)).astype(np.float32),
                   rng.integers(0, 6, n_per)) for _ in range(m)]
        inits = [cnn_init(jax.random.PRNGKey(i), tspec) for i in range(m)]
        xs, ys = pad_shards(shards)
        plan = build_batch_plan([n_per] * m, batch, epochs=epochs,
                                seeds=list(range(m)))
        run, opt = make_grouped_local_update(tspec, lr=0.01, momentum=0.9,
                                             use_ldam=False)
        margins = jnp.zeros((m, 6))
        args0 = (jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(plan.idx),
                 jnp.asarray(plan.mask), margins)
        sharded = group_shardable(mesh, m)
        args_sh = put_stacked(args0, mesh, m) if sharded else args0

        def one_pass(args):
            stacked0 = jax.tree.map(lambda *a: jnp.stack(a), *inits)
            state = opt.init(stacked0)
            if args is args_sh and sharded:
                stacked0, state = put_stacked((stacked0, state), mesh, m)
            p, s, losses = run(stacked0, state, *args)
            jax.block_until_ready(losses)

        t_one, t_sh = time_ab(one_pass, (args0,), one_pass, (args_sh,),
                              warmup=2, iters=7 if not full else 15)
        steps = m * epochs * (-(-n_per // batch))
        emit(f"s/local_train/single/m{m}", t_one / steps,
             f"clients_per_sec={m / t_one:.2f}")
        emit(f"s/local_train/sharded/m{m}", t_sh / steps,
             (f"clients_per_sec={m / t_sh:.2f};clients_axis={c};"
              f"sharded={sharded};speedup={t_one / t_sh:.2f}x"))


def bk_backend(full: bool):
    """BK: the backend execution-policy registry (configs/backend.py,
    DESIGN.md §11). Per kernel pair, forward µs at the registry-default
    block table vs the committed seed-cache autotuned blocks for a
    shape whose bucket the seed actually tuned (the 512-dim buckets,
    where the tuned choice differs from the table). Interpret-mode
    timings on this shared CPU host are jittery, so the default vs
    autotuned contrast is trajectory data, not a claim — the gateable
    series are each column against its own history. Plus the
    resolve_exec_policy overhead itself:
    cold (memos dropped, cache-file stat + profile build) and warm
    (memo hit) — warm is what every make_*_steps call pays."""
    from repro.configs import backend as B
    from repro.kernels import ops

    scfg = base_cfg(full)
    B.resolve_exec_policy(scfg)                     # prime the memo
    for variant, prep in (("cold", B.clear_caches), ("warm", lambda: None)):
        ts = []
        for _ in range(50):
            prep()
            t0 = time.perf_counter()
            B.resolve_exec_policy(scfg)
            ts.append(time.perf_counter() - t0)
        emit(f"bk/resolve/{variant}", float(np.median(ts)),
             f"iters=50;backend={B.detect_backend(scfg)}")

    pol = B.resolve_exec_policy(None).replace(kernel_vjp="autodiff")
    key = jax.random.PRNGKey(0)
    t_ = jax.random.normal(key, (512, 4096)) * 3
    s_ = jax.random.normal(jax.random.PRNGKey(1), (512, 4096)) * 3
    q = jax.random.normal(key, (1, 2, 512, 16))
    x = jax.random.normal(key, (1, 512, 2, 8))
    dt_in = jax.nn.softplus(jax.random.normal(key, (1, 512, 2)))
    a = -jnp.exp(jax.random.normal(key, (2,)) * 0.3)
    bm = jax.random.normal(key, (1, 512, 1, 8)) * 0.3
    cases = (
        ("distill_kl", (512, 4096),
         lambda p: ops.distill_kl(t_, s_, policy=p)),
        ("flash_attention", (512, 512),
         lambda p: ops.flash_attention(q, q, q, policy=p)),
        ("ssd_scan", (512,),
         lambda p: ops.ssd_scan(x, dt_in, a, bm, bm, policy=p)),
    )
    iters = 3 if full else 2
    for kernel, shape, call in cases:
        names = B.KERNEL_BLOCK_ARGS[kernel]
        default = pol.blocks_for(kernel)            # registry table
        tuned = B.autotune_blocks(kernel, shape, pol)  # seed-cache hit
        p_def = pol.override_blocks(kernel, **dict(zip(names, default)))
        p_tun = pol.override_blocks(kernel, **dict(zip(names, tuned)))
        t_def, t_tun = time_ab(call, (p_def,), call, (p_tun,),
                               warmup=1, iters=iters)
        sh = "x".join(str(d) for d in shape)
        emit(f"bk/{kernel}/default/{sh}", t_def, f"blocks={default}")
        emit(f"bk/{kernel}/autotuned/{sh}", t_tun,
             f"blocks={tuned};speedup={t_def / t_tun:.2f}x")


def serve_table(full: bool):
    """SERVE: request-level serving (launch/engine.py, DESIGN.md §12).
    Paged continuous batching vs the sequential dense reference under a
    seeded synthetic Poisson arrival trace, at two regimes: ``trickle``
    (arrivals spread out — continuous batching earns little) and
    ``burst`` (a queue forms at t=0 — the paged engine's fused decode
    step over all slots is the win). Arrival times are in scheduler
    steps, not wall-clock, so the trace is identical for both engines
    and across runs. Emits wall seconds per run; the derived column
    carries tok_per_sec and p50/p99 per-request latency (submit→done,
    so queueing counts). First-request latency includes jit warmup on
    both sides — trajectory data, same caveat as the BK table."""
    from repro.configs.base import get_smoke_config
    from repro.launch.engine import ServeEngine, engine_keys

    cfg = get_smoke_config("llama3.2-3b")
    gen = 16 if full else 8
    plens = (6, 10)                       # two jit buckets, ragged batch
    k_init, k_prompt, _ = engine_keys(0)
    from repro.models import transformer as T
    params = T.init_model(k_init, cfg)
    rng = np.random.default_rng(9)        # the seeded Poisson trace

    def drive(mode, n, rate, max_reqs):
        prompts = [np.asarray(jax.random.randint(
            jax.random.fold_in(k_prompt, i), (plens[i % 2],), 0,
            cfg.vocab_size), np.int32) for i in range(n)]
        arrive = np.floor(np.cumsum(
            rng.exponential(1.0 / rate, n))).astype(int) if rate > 0 \
            else np.zeros(n, int)
        eng = ServeEngine(cfg, params, mode=mode, max_reqs=max_reqs,
                          max_len=max(plens) + gen, seed=0)
        rids, i, step = [], 0, 0
        limit = int(arrive.max(initial=0)) + 4 * n * (gen + 2) + 50
        t0 = time.perf_counter()
        while i < n or any(eng.poll(r)["status"] != "done" for r in rids):
            while i < n and arrive[i] <= step:
                rids.append(eng.submit(prompts[i], max_new=gen))
                i += 1
            eng.step()
            step += 1
            if step > limit:
                raise RuntimeError("serve bench scheduler stuck")
        wall = time.perf_counter() - t0
        lat = np.asarray([eng.poll(r)["latency_s"] for r in rids])
        return wall, n * gen / wall, lat

    regimes = (("trickle", 4, 0.25, 2), ("burst", 8 if full else 6, 0.0, 4))
    for regime, n, rate, max_reqs in regimes:
        walls = {}
        for mode in ("paged", "dense"):
            # same rng state for both engines: re-seed per run so the
            # two modes see the identical arrival trace
            rng = np.random.default_rng(9)
            wall, tps, lat = drive(mode, n, rate, max_reqs)
            walls[mode] = wall
            emit(f"serve/{mode}/{regime}", wall,
                 (f"tok_per_sec={tps:.1f};"
                  f"p50_ms={np.percentile(lat, 50) * 1e3:.1f};"
                  f"p99_ms={np.percentile(lat, 99) * 1e3:.1f};"
                  f"reqs={n};gen={gen};slots={max_reqs}"))
        emit(f"serve/paged_vs_dense/{regime}", 0.0,
             f"speedup={walls['dense'] / walls['paged']:.2f}x")


def r_roofline(full: bool):
    """Summarize dry-run artifacts (run repro.launch.dryrun first)."""
    files = sorted(glob.glob(os.path.join(
        os.path.dirname(__file__), "..", "artifacts", "dryrun", "*.json")))
    if not files:
        emit("r/roofline", 0.0,
             "no_artifacts;run=python -m repro.launch.dryrun --all")
        return
    for f in files:
        rec = json.load(open(f))
        tag = f"r/{rec['arch']}/{rec['shape']}/{rec['mesh']}"
        if rec.get("status") != "ok":
            emit(tag, 0.0, f"status={rec.get('status')}")
            continue
        t = rec.get("roofline") or rec["roofline_raw"]
        emit(tag, rec.get("compile_s", 0.0),
             (f"bottleneck={rec['bottleneck']};"
              f"compute_s={t['compute_s']:.4f};"
              f"memory_s={t['memory_s']:.4f};"
              f"collective_s={t['collective_s']:.6f};"
              f"useful_ratio={rec.get('useful_flops_ratio', 0.0):.3f}"))


def r_robustness(full: bool):
    """Fault-tolerant one-shot round (DESIGN.md §10): DENSE accuracy and
    local-phase throughput as the per-round client dropout fraction
    grows under quarantine admission, plus the stage-2 checkpointing
    overhead and a kill+resume round trip."""
    import shutil
    import tempfile

    from repro.core.dense import train_dense_server
    from repro.data import make_classification_data
    from repro.fl import build_federation

    base = dataclasses.replace(
        base_cfg(full), n_clients=5, client_kinds=("cnn1",) * 5,
        quorum=0.2, fault_seed=1)
    fracs = (0.0, 0.1, 0.3, 0.5) if full else (0.0, 0.3, 0.5)
    for frac in fracs:
        scfg = dataclasses.replace(base, dropout_frac=frac)
        data, clients, _ = get_federation(scfg)
        # time the local phase + fault/admission boundary fresh (the
        # cached build above only warmed data + compilation)
        t0 = time.time()
        fresh, _ = build_federation(jax.random.PRNGKey(0), scfg, data,
                                    seed=0)
        t_build = time.time() - t0
        m = scfg.n_clients
        surv = int(getattr(fresh, "survivor_mask",
                           np.ones(m, bool)).sum())
        acc, dt = run_method("dense", scfg)
        emit(f"r/local_train/frac{frac}", t_build / m,
             f"clients_per_sec={m / t_build:.2f};survivors={surv}/{m}")
        emit(f"r/dense/frac{frac}", dt,
             f"acc={acc:.4f};survivors={surv}/{m}")

    # checkpointing overhead + kill/resume round trip (quarantine-free)
    data, clients, _ = get_federation(base)
    key = jax.random.PRNGKey(100)
    t0 = time.time()
    train_dense_server(key, clients, base)
    t_plain = time.time() - t0
    ckdir = tempfile.mkdtemp(prefix="dense_bench_ck_")
    try:
        every = max(2, base.epochs // 5)
        scfg_ck = dataclasses.replace(
            base, checkpoint_every=every,
            checkpoint_path=os.path.join(ckdir, "ck"))
        t0 = time.time()
        train_dense_server(key, clients, scfg_ck)
        t_ck = time.time() - t0
        emit("r/checkpoint_overhead", t_ck,
             (f"every={every};overhead={t_ck / t_plain:.3f}x;"
              f"plain_s={t_plain:.2f}"))
        # kill at ~60% of the run, resume from the last checkpoint
        shutil.rmtree(ckdir)
        os.makedirs(ckdir)
        stop = (base.epochs * 3) // 5
        t0 = time.time()
        train_dense_server(key, clients, scfg_ck,
                           _stop_after_epoch=stop)
        train_dense_server(key, clients, scfg_ck)
        t_resume = time.time() - t0
        emit("r/kill_resume", t_resume,
             (f"stop_epoch={stop};roundtrip_vs_plain="
              f"{t_resume / t_plain:.3f}x"))
    finally:
        shutil.rmtree(ckdir, ignore_errors=True)


def m_scaling(full: bool):
    """Federation-axis scaling (DESIGN.md §13): the m in {10,100,1000}
    curve behind the bucketed/chunked engine. Per m: local-phase
    clients/sec under quantile buckets + chunked group setup, padded-step
    waste per bucketing mode on a Dirichlet alpha=0.1 partition, host
    peak RSS, tree-vs-flat fedavg and the chunked ensemble teacher. A
    heterogeneous (cnn1+cnn2) point rides at the largest m to pin the
    multi-group path."""
    import resource

    from repro.configs.backend import resolve_exec_policy
    from repro.data.partition import dirichlet_partition
    from repro.data.pipeline import plan_step_waste
    from repro.core.ensemble import grouped_ensemble_logits
    from repro.fl import fedavg_stacked, train_clients_grouped
    from repro.models.cnn import CNNSpec

    spec_kw = dict(num_classes=4, in_ch=1, width=0.25, image_size=8)
    batch = 16

    def rss_mb():
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024

    def build(m, kinds, seed=0):
        rng = np.random.default_rng(seed)
        y = rng.integers(0, 4, max(8 * m, 2000))
        parts = dirichlet_partition(y, m, 0.1, seed=seed)
        sizes = [max(2, len(p)) for p in parts]
        shards = [(rng.standard_normal((n, 8, 8, 1)).astype(np.float32),
                   rng.integers(0, 4, n)) for n in sizes]
        specs = [CNNSpec(kind=kinds[i % len(kinds)], **spec_kw)
                 for i in range(m)]
        return specs, shards, sizes

    pol = resolve_exec_policy(SimpleNamespaceCfg())
    ms = (10, 100, 1000)
    for m in ms:
        specs, shards, sizes = build(m, ("cnn1",))
        for mode in ("off", "pow2", "quantile"):
            w = plan_step_waste(sizes, batch, mode)
            emit(f"m/plan_waste_{mode}/m{m}", 0.0,
                 f"waste={w:.4f};batch={batch}")
        keys = list(jax.random.split(jax.random.PRNGKey(1), m))
        t0 = time.time()
        clients = train_clients_grouped(
            specs, shards, epochs=1, lr=0.05, momentum=0.9,
            batch_size=batch, use_ldam=False, num_classes=4,
            seeds=list(range(m)), init_keys=keys, policy=pol)
        dt = time.time() - t0
        emit(f"m/local_train/m{m}", dt / m,
             f"clients_per_sec={m / dt:.2f};rss_mb={rss_mb():.0f}")
        gspecs, gparams = clients.grouped
        n_data = [c.n_data for c in clients]
        t_flat = time_call(lambda: fedavg_stacked(gparams[0], n_data))
        t_tree = time_call(lambda: fedavg_stacked(
            gparams[0], n_data, mode="tree", branch=pol.fedavg_branch))
        emit(f"m/fedavg_tree/m{m}", t_tree,
             f"branch={pol.fedavg_branch};flat_s={t_flat:.4f};"
             f"speedup={t_flat / t_tree:.2f}x")
        x = jnp.asarray(np.random.default_rng(2).standard_normal(
            (batch, 8, 8, 1)).astype(np.float32))
        t_full = time_call(lambda: grouped_ensemble_logits(
            gspecs, gparams, x))
        t_chunk = time_call(lambda: grouped_ensemble_logits(
            gspecs, gparams, x, chunk=pol.teacher_chunk))
        emit(f"m/teacher_chunked/m{m}", t_chunk,
             f"chunk={pol.teacher_chunk};full_s={t_full:.4f};"
             f"rss_mb={rss_mb():.0f}")

    # heterogeneous point at the curve's top: multi-group bucketing
    m = ms[-1] if full else ms[-2]
    specs, shards, _ = build(m, ("cnn1", "cnn2"), seed=3)
    keys = list(jax.random.split(jax.random.PRNGKey(4), m))
    t0 = time.time()
    train_clients_grouped(
        specs, shards, epochs=1, lr=0.05, momentum=0.9, batch_size=batch,
        use_ldam=False, num_classes=4, seeds=list(range(m)),
        init_keys=keys, policy=pol)
    dt = time.time() - t0
    emit(f"m/local_train_hetero/m{m}", dt / m,
         f"clients_per_sec={m / dt:.2f};groups=2;rss_mb={rss_mb():.0f}")


class SimpleNamespaceCfg:
    """Minimal scfg for the scale table: every federation-scale knob on,
    everything else at registry defaults."""
    plan_bucketing = "quantile"
    stack_chunk = 64
    fedavg_mode = "tree"
    fedavg_branch = 8
    teacher_chunk = 64


TABLES = {"t1": t1_alpha_sweep, "t2": t2_heterogeneous, "t3": t3_num_clients,
          "t4": t4_ldam, "t5": t5_multiround, "t6": t6_ablation,
          "f3": f3_local_vs_global, "k": k_kernels, "kl": kl_distill,
          "attn": attn_flash, "ssd": ssd_table, "e": e_ensemble,
          "c": c_client_training, "s": s_sharding, "r": r_robustness,
          "bk": bk_backend, "serve": serve_table, "roof": r_roofline,
          "m": m_scaling}


def main() -> None:
    from benchmarks.common import write_json
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="EXPERIMENTS.md budget (slow)")
    ap.add_argument("--only", default=None,
                    help="comma list of tables, e.g. t1,t6,k")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write records + per-table medians as JSON "
                         "(the BENCH_PR9.json trajectory artifact)")
    args = ap.parse_args()
    names = args.only.split(",") if args.only else list(TABLES)
    print("name,us_per_call,derived", flush=True)
    for n in names:
        TABLES[n](args.full)
    if args.json:
        write_json(args.json)


if __name__ == "__main__":
    main()
