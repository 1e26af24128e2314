"""Backend execution-policy registry tests (configs/backend.py,
DESIGN.md §11): detection precedence, per-scfg knob precedence, the
legacy-kwarg deprecation shim, autotune-cache behavior (hits skip
timing; corruption degrades with a warning; tie-breaking is
deterministic), bit-stable resolution, and the AST enforcement sweep
that keeps configs/backend.py the ONLY module deciding modes/blocks."""
import ast
import json
import os
import warnings
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from repro.configs import backend as B
from repro.kernels import ops

SRC = os.path.join(os.path.dirname(__file__), "..", "src", "repro")


@pytest.fixture(autouse=True)
def _isolated_registry(monkeypatch, tmp_path):
    """Every test gets a private writable cache and clean memos; the
    committed seed cache stays visible (it is part of the contract)."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    monkeypatch.delenv("REPRO_INTERPRET", raising=False)
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    B.clear_caches()
    yield
    B.clear_caches()


# ------------------------------------------------------------- detection

def test_backend_env_override(monkeypatch):
    assert B.detect_backend(None) == jax.default_backend()
    monkeypatch.setenv("REPRO_BACKEND", "gpu")
    assert B.detect_backend(None) == "gpu"
    pol = B.resolve_exec_policy(None)
    assert (pol.backend, pol.loop, pol.distill_kl, pol.kernel_vjp) == \
        ("gpu", "fused", "fused", "fused")
    # scfg.backend beats the env var
    assert B.detect_backend(SimpleNamespace(backend="tpu")) == "tpu"
    with pytest.raises(ValueError, match="unknown backend"):
        B.detect_backend(SimpleNamespace(backend="mps"))


def test_gpu_profile_not_interpret(monkeypatch):
    """The _auto_interpret bugfix: gpu must NOT silently run interpret
    mode (only cpu defaults to interpret=True), and REPRO_INTERPRET
    overrides the registry in both directions."""
    assert B.resolve_exec_policy(None, backend="cpu").interpret is True
    assert B.resolve_exec_policy(None, backend="gpu").interpret is False
    assert B.resolve_exec_policy(None, backend="tpu").interpret is False
    monkeypatch.setenv("REPRO_INTERPRET", "1")
    assert B.resolve_exec_policy(None, backend="gpu").interpret is True
    monkeypatch.setenv("REPRO_INTERPRET", "0")
    assert B.resolve_exec_policy(None, backend="cpu").interpret is False


# ------------------------------------------------------------ precedence

def test_scfg_knobs_beat_registry():
    scfg = SimpleNamespace(loop_mode="fused", distill_kl_mode="fused",
                           ensemble_shard_mode="clients")
    pol = B.resolve_exec_policy(scfg, backend="cpu")
    assert pol.loop == "fused"
    assert pol.distill_kl == "fused"
    assert pol.ensemble_shard == "clients"
    # unset knobs fall through to the cpu profile
    assert pol.client_loop == "grouped"
    assert pol.kernel_vjp == "ref"


def test_resolution_validates_modes():
    with pytest.raises(ValueError, match="unknown loop_mode"):
        B.resolve_exec_policy(SimpleNamespace(loop_mode="vectorized"))
    with pytest.raises(ValueError, match="unknown client_loop_mode"):
        B.resolve_exec_policy(SimpleNamespace(client_loop_mode="batched"))
    with pytest.raises(ValueError, match="unknown ensemble_shard_mode"):
        B.resolve_exec_policy(SimpleNamespace(ensemble_shard_mode="data"))
    with pytest.raises(ValueError, match="unknown distill_kl mode"):
        B.resolve_exec_policy(SimpleNamespace(distill_kl_mode="pallas"))
    with pytest.raises(ValueError, match="unknown kernel_vjp mode"):
        B.resolve_exec_policy(SimpleNamespace(kernel_vjp_mode="nope"))


def test_kernel_blocks_override_precedence():
    scfg = SimpleNamespace(kernel_blocks=(("distill_kl", (128, 1024)),))
    pol = B.resolve_exec_policy(scfg, backend="cpu")
    assert pol.blocks_for("distill_kl") == (128, 1024)
    # other kernels keep the registry table
    assert pol.blocks_for("flash_attention") == (128, 128)
    # mapping form with named values, None inherits per position
    scfg2 = SimpleNamespace(
        kernel_blocks={"flash_attention": {"block_q": 64}})
    pol2 = B.resolve_exec_policy(scfg2, backend="cpu")
    assert pol2.blocks_for("flash_attention") == (64, 128)
    with pytest.raises(ValueError, match="unknown kernel"):
        B.resolve_exec_policy(
            SimpleNamespace(kernel_blocks={"matmul": (8,)}))


def test_override_blocks_method():
    pol = B.resolve_exec_policy(None, backend="cpu")
    pol2 = pol.override_blocks("ssd_scan", chunk=32)
    assert pol2.blocks_for("ssd_scan") == (32,)
    assert pol.blocks_for("ssd_scan") == (128,)     # frozen original
    with pytest.raises(ValueError, match="unknown block args"):
        pol.override_blocks("ssd_scan", block_q=8)


def test_resolution_bit_stable():
    scfg = SimpleNamespace(loop_mode="fused")
    a = B.resolve_exec_policy(scfg, backend="cpu")
    b = B.resolve_exec_policy(scfg, backend="cpu")
    assert a == b and hash(a) == hash(b)
    # idempotent: resolving a policy returns it unchanged
    assert B.resolve_exec_policy(a) is a


# -------------------------------------------------------- legacy shim

def test_flash_shim_equivalent_to_policy():
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 2, 16, 8))
    pol = B.resolve_exec_policy(None, backend="cpu").replace(
        kernel_vjp="autodiff").override_blocks(
            "flash_attention", block_q=8, block_k=8)
    with pytest.warns(DeprecationWarning, match="flash_attention"):
        old = ops.flash_attention(q, q, q, block_q=8, block_k=8,
                                  interpret=True)
    new = ops.flash_attention(q, q, q, policy=pol)
    assert jnp.allclose(old, new, atol=1e-6)


def test_ssd_shim_equivalent_to_policy():
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (1, 32, 2, 4))
    dt = jnp.full((1, 32, 2), 0.1)
    a = -jnp.ones((2,))
    bm = jax.random.normal(jax.random.PRNGKey(2), (1, 32, 2, 4))
    pol = B.resolve_exec_policy(None, backend="cpu").replace(
        kernel_vjp="autodiff").override_blocks("ssd_scan", chunk=16)
    with pytest.warns(DeprecationWarning, match="ssd_scan"):
        old_y, old_s = ops.ssd_scan(x, dt, a, bm, bm, chunk=16,
                                    interpret=True)
    new_y, new_s = ops.ssd_scan(x, dt, a, bm, bm, policy=pol)
    assert jnp.allclose(old_y, new_y, atol=1e-6)
    assert jnp.allclose(old_s, new_s, atol=1e-6)


def test_distill_kl_shim_equivalent_to_policy():
    t = jax.random.normal(jax.random.PRNGKey(3), (8, 64))
    s = jax.random.normal(jax.random.PRNGKey(4), (8, 64))
    pol = B.resolve_exec_policy(None, backend="cpu").override_blocks(
        "distill_kl", block_rows=4, block_v=32)
    with pytest.warns(DeprecationWarning, match="distill_kl"):
        old = ops.distill_kl(t, s, 4, 32)
    new = ops.distill_kl(t, s, policy=pol)
    assert jnp.allclose(old, new, atol=1e-6)


def test_policy_path_emits_no_warning():
    t = jnp.zeros((4, 32))
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        ops.distill_kl(t, t)
        ops.flash_attention(jnp.zeros((1, 1, 8, 4)), jnp.zeros((1, 1, 8, 4)),
                            jnp.zeros((1, 1, 8, 4)))


# ---------------------------------------------------------- autotuner

def _write_cache(path, entries):
    with open(path, "w") as f:
        json.dump({"version": 1, "entries": entries}, f)
    B.clear_caches()


def test_cache_hit_skips_timing(monkeypatch, tmp_path):
    path = tmp_path / "autotune.json"
    _write_cache(path, {"cpu/distill_kl/64x128":
                        {"blocks": {"block_rows": 32, "block_v": 64},
                         "us": 1.0}})
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")

    def boom(fn, reps=3):
        raise AssertionError("timer ran on a cache hit")

    monkeypatch.setattr(B, "_timer", boom)
    pol = B.resolve_exec_policy(None, backend="cpu")
    assert B.autotune_blocks("distill_kl", (40, 100), pol) == (32, 64)
    # the resolved policy carries the tuned entry for blocks_for too
    assert pol.blocks_for("distill_kl", (40, 100)) == (32, 64)
    assert pol.blocks_for("distill_kl", (40, 4000)) == (256, 2048)


def test_autotune_disabled_returns_registry(monkeypatch):
    monkeypatch.setattr(B, "_timer",
                        lambda fn, reps=3: pytest.fail("timed while off"))
    pol = B.resolve_exec_policy(None, backend="cpu")
    # bucket 64x64 is deliberately absent from the committed seed cache
    assert B.autotune_blocks("flash_attention", (33, 33), pol) == \
        pol.blocks_for("flash_attention")
    assert not os.path.exists(B._default_cache_path())


def test_corrupt_cache_warns_and_falls_back(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")   # the file is read only then
    path = tmp_path / "autotune.json"
    path.write_text("{not json")
    B.clear_caches()
    with pytest.warns(UserWarning, match="unreadable autotune cache"):
        pol = B.resolve_exec_policy(None, backend="cpu")
    assert pol.blocks_for("distill_kl") == (256, 2048)


def test_stale_cache_version_warns_and_falls_back(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    path = tmp_path / "autotune.json"
    path.write_text(json.dumps({"version": 99, "entries": {}}))
    B.clear_caches()
    with pytest.warns(UserWarning, match="unreadable autotune cache"):
        pol = B.resolve_exec_policy(None, backend="cpu")
    assert pol.blocks_for("ssd_scan") == (128,)


def test_writable_cache_read_only_when_autotuning(monkeypatch, tmp_path):
    """Without REPRO_AUTOTUNE=1 the blocks come from committed files
    alone: the writable cache outside the checkout is never read."""
    _write_cache(tmp_path / "autotune.json",
                 {"cpu/distill_kl/64x128":
                  {"blocks": {"block_rows": 32, "block_v": 64}, "us": 1.0}})
    pol = B.resolve_exec_policy(None, backend="cpu")
    assert pol.blocks_for("distill_kl", (40, 100)) == (256, 2048)
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    pol = B.resolve_exec_policy(None, backend="cpu")
    assert pol.blocks_for("distill_kl", (40, 100)) == (32, 64)


def test_deterministic_winner_under_ties(monkeypatch, tmp_path):
    """All candidates time identically → the EARLIEST candidate in
    canonical _CANDIDATES order wins, every run."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    monkeypatch.setattr(B, "_timer", lambda fn, reps=3: 100.0)
    monkeypatch.setattr(B, "_candidate_runner",
                        lambda *a, **k: (lambda: None))
    pol = B.resolve_exec_policy(None, backend="cpu")
    won = B.autotune_blocks("distill_kl", (1000, 4000), pol)
    assert won == B._CANDIDATES["distill_kl"][0] == (256, 2048)
    # persisted: a second resolution sees it as a cache hit
    doc = json.loads(open(B._default_cache_path()).read())
    assert doc["entries"]["cpu/distill_kl/1024x4096"]["blocks"] == \
        {"block_rows": 256, "block_v": 2048}
    monkeypatch.setattr(B, "_timer",
                        lambda fn, reps=3: pytest.fail("re-timed a hit"))
    assert B.autotune_blocks("distill_kl", (1000, 4000), pol) == won


def test_candidates_clamped_and_deduped(monkeypatch):
    """Tiny problems clamp every candidate to the same shape — exactly
    one timing run, winner equals the clamped shape."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    calls = []

    def fake_timer(fn, reps=3):
        calls.append(1)
        return 5.0

    monkeypatch.setattr(B, "_timer", fake_timer)
    monkeypatch.setattr(B, "_candidate_runner",
                        lambda *a, **k: (lambda: None))
    pol = B.resolve_exec_policy(None, backend="cpu")
    assert B.autotune_blocks("ssd_scan", (16,), pol) == (16,)
    assert len(calls) == 1


def test_shape_bucket():
    assert B.shape_bucket("distill_kl", (40, 100)) == "64x128"
    assert B.shape_bucket("flash_attention", (128, 128)) == "128x128"
    assert B.shape_bucket("ssd_scan", (1,)) == "1"


def test_seed_cache_is_valid():
    """The committed seed cache must parse cleanly (no warning) and only
    contain known backends/kernels with well-formed block values."""
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        entries = B._read_cache_file(B._SEED_CACHE)
    assert entries, "seed cache missing or empty"
    for (backend, kernel, bucket), vals in entries.items():
        assert backend in B.BACKENDS
        assert len(vals) == len(B.KERNEL_BLOCK_ARGS[kernel])
        assert all(isinstance(v, int) and v > 0 for v in vals)


# ------------------------------------------------- federation-scale knobs

def test_scale_knobs_resolve_and_validate():
    """The DESIGN.md §13 knobs route through the registry like every
    other mode: scfg beats profile, defaults keep every knob off
    (= bit-compatible m=10 path), unknown values fail loudly."""
    scfg = SimpleNamespace(plan_bucketing="pow2", stack_chunk=16,
                           fedavg_mode="tree", fedavg_branch=4,
                           teacher_chunk=8)
    pol = B.resolve_exec_policy(scfg, backend="cpu")
    assert (pol.bucketing, pol.stack_chunk, pol.fedavg,
            pol.fedavg_branch, pol.teacher_chunk) == \
        ("pow2", 16, "tree", 4, 8)
    for bk in B.BACKENDS:
        d = B.resolve_exec_policy(None, backend=bk)
        assert (d.bucketing, d.stack_chunk, d.fedavg, d.teacher_chunk) \
            == ("off", 0, "flat", 0)
    with pytest.raises(ValueError, match="unknown plan_bucketing"):
        B.resolve_exec_policy(SimpleNamespace(plan_bucketing="bins"))
    with pytest.raises(ValueError, match="unknown fedavg_mode"):
        B.resolve_exec_policy(SimpleNamespace(fedavg_mode="ring"))


# --------------------------------------------- backward-kernel autotune

def test_bwd_kernel_entries_resolve():
    """``{kernel}_bwd`` is a first-class registry row: its own defaults,
    candidates and overrides, never aliased to the forward entry."""
    pol = B.resolve_exec_policy(None, backend="cpu")
    assert pol.blocks_for("distill_kl_bwd") == (256, 2048)
    assert pol.blocks_for("flash_attention_bwd") == (128, 128)
    assert "ssd_scan_bwd" not in B.KERNEL_BLOCK_ARGS   # documented exception
    scfg = SimpleNamespace(
        kernel_blocks={"distill_kl_bwd": {"block_rows": 64}})
    pol2 = B.resolve_exec_policy(scfg, backend="cpu")
    assert pol2.blocks_for("distill_kl_bwd") == (64, 2048)
    assert pol2.blocks_for("distill_kl") == (256, 2048)  # fwd untouched


def test_bwd_override_skips_autotune(monkeypatch):
    """ops._bwd_blocks precedence: an explicit _bwd override wins even
    with REPRO_AUTOTUNE=1 — no timing run may fire (timer raises)."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    monkeypatch.setattr(B, "_timer", lambda *a, **k: (_ for _ in ()).throw(
        AssertionError("timed despite override")))
    scfg = SimpleNamespace(
        kernel_blocks={"flash_attention_bwd": (64, 64)})
    pol = B.resolve_exec_policy(scfg, backend="cpu")
    assert ops._bwd_blocks("flash_attention", pol, (128, 128)) == (64, 64)


def test_bwd_autotune_disabled_returns_registry():
    pol = B.resolve_exec_policy(None, backend="cpu")
    assert ops._bwd_blocks("distill_kl", pol, (999, 999)) == \
        pol.blocks_for("distill_kl_bwd", (999, 999))


def test_seed_cache_covers_bwd_kernels():
    """The committed seed cache pins backward winners too, so CI never
    times (or silently falls back) on the tuned-backward path."""
    entries = B._read_cache_file(B._SEED_CACHE)
    kernels = {k for (_, k, _) in entries}
    assert {"distill_kl_bwd", "flash_attention_bwd"} <= kernels


# ------------------------------------------------- AST enforcement sweep

_BANNED_ATTRS = {"loop_mode", "client_loop_mode", "ensemble_shard_mode",
                 "distill_kl_mode", "kernel_vjp_mode", "plan_bucketing",
                 "fedavg_mode"}
_BLOCK_NAMES = {"block_q", "block_k", "block_rows", "block_v", "chunk",
                "page"}


def _src_files():
    for root, dirs, files in os.walk(SRC):
        if os.path.basename(root) == "configs":
            dirs[:] = []
            continue
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in sorted(files):
            if f.endswith(".py"):
                yield os.path.join(root, f)


def test_no_raw_knob_reads_outside_configs():
    """Outside configs/, no module may read the mode knobs off a config
    (attribute access or getattr-by-string) — resolve_exec_policy is the
    only resolution point. Docstrings/comments are naturally exempt."""
    bad = []
    for path in _src_files():
        tree = ast.parse(open(path).read(), filename=path)
        for node in ast.walk(tree):
            if isinstance(node, ast.Attribute) and \
                    node.attr in _BANNED_ATTRS:
                bad.append(f"{path}:{node.lineno} .{node.attr}")
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Name) and \
                    node.func.id == "getattr" and len(node.args) >= 2 and \
                    isinstance(node.args[1], ast.Constant) and \
                    node.args[1].value in _BANNED_ATTRS:
                bad.append(f"{path}:{node.lineno} "
                           f"getattr(..., {node.args[1].value!r})")
    assert not bad, "raw mode-knob reads outside configs/:\n" + \
        "\n".join(bad)


def test_no_hardcoded_block_shapes_outside_configs():
    """Outside configs/, no call may pass a literal int for a kernel
    block argument and no function may default one to a literal int —
    block shapes come from the registry/autotuner via ExecPolicy."""
    bad = []
    for path in _src_files():
        tree = ast.parse(open(path).read(), filename=path)
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                for kw in node.keywords:
                    if kw.arg in _BLOCK_NAMES and \
                            isinstance(kw.value, ast.Constant) and \
                            isinstance(kw.value.value, int):
                        bad.append(f"{path}:{node.lineno} "
                                   f"{kw.arg}={kw.value.value}")
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                a = node.args
                pos = a.posonlyargs + a.args
                for arg, dflt in zip(pos[len(pos) - len(a.defaults):],
                                     a.defaults):
                    if arg.arg in _BLOCK_NAMES and \
                            isinstance(dflt, ast.Constant) and \
                            isinstance(dflt.value, int):
                        bad.append(f"{path}:{node.lineno} def "
                                   f"{node.name}({arg.arg}={dflt.value})")
                for arg, dflt in zip(a.kwonlyargs, a.kw_defaults):
                    if dflt is not None and arg.arg in _BLOCK_NAMES and \
                            isinstance(dflt, ast.Constant) and \
                            isinstance(dflt.value, int):
                        bad.append(f"{path}:{node.lineno} def "
                                   f"{node.name}({arg.arg}={dflt.value})")
    assert not bad, "hardcoded block shapes outside configs/:\n" + \
        "\n".join(bad)
