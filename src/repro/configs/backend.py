"""Backend-aware execution-policy registry (DESIGN.md §11).

One resolution API for every mode knob. Historically the repo carried
four execution-mode knobs (``loop_mode``, ``ensemble_shard_mode``,
``distill_kl_mode``, ``kernel_vjp_mode`` — plus ``client_loop_mode``)
that each defaulted to CPU-friendly settings with "flip when an
accelerator lands" folklore in their comments, and hardcoded Pallas
block shapes threaded as per-call kwargs through the kernel wrappers.
This module is now the ONLY place those decisions are made:

  * ``resolve_exec_policy(scfg)`` — scfg knobs (when set) override the
    per-backend registry defaults; the result is a frozen, hashable
    ``ExecPolicy`` consumed by core/dense.py, core/dense_llm.py,
    launch/steps.py, fl/protocol.py, fl/sharding.py, fl/client.py and
    kernels/ops.py. A grep-enforcement test (tests/test_backend.py)
    bans raw knob reads and literal block-shape kwargs everywhere else.
  * ``arch_policy(cfg)`` — the model-layer variant: ArchConfig's
    ``kernel_vjp_mode`` / ``attn_block_q`` / ``attn_block_kv`` /
    ``ssm_chunk`` become explicit overrides on the registry policy.
  * a lightweight autotuner that times candidate block shapes for the
    three kernel pairs at first trace and caches the winner per
    ``(backend, kernel, shape-bucket)`` in an on-disk JSON cache with
    deterministic tie-breaking (earliest candidate wins ties).

Backend detection precedence: ``scfg.backend`` > ``REPRO_BACKEND`` env
> ``jax.default_backend()``. Interpret-mode: from the registry
(cpu → True, gpu/tpu → False), overridable by ``REPRO_INTERPRET``
("1"/"0") — this also fixes the old ``_auto_interpret`` bug where a GPU
backend silently ran every kernel in interpret mode. A committed seed
cache (configs/autotune_seed.json) is always loaded, so CI timing noise
never changes selected blocks. ``REPRO_AUTOTUNE=1`` enables timing on
cache miss and overlays the writable cache (``REPRO_AUTOTUNE_CACHE``,
default ``~/.cache/repro-dense/autotune.json``); without it that file is
never read, so the blocks a run uses come from committed files alone.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
import warnings
from dataclasses import dataclass

BACKENDS = ("cpu", "gpu", "tpu")

# mode vocabularies (single source of truth; fl/sharding, core/losses and
# kernels/ops re-export their historical names for compatibility)
LOOP_MODES = ("python", "fused")
CLIENT_LOOP_MODES = ("python", "grouped")
SHARD_MODES = ("none", "clients")
KL_MODES = ("ref", "fused")
KERNEL_VJP_MODES = ("ref", "autodiff", "fused")
BUCKETING_MODES = ("off", "pow2", "quantile")
FEDAVG_MODES = ("flat", "tree")

# the three custom-VJP kernel pairs and their block-shape argument names,
# in canonical order (DESIGN.md §9), plus the forward-only serving
# kernel (§12; its "page" is the block-pool page size — a cache *layout*
# parameter consumed at allocation time by launch/paging.py, not a
# per-call kwarg). The ``*_bwd`` entries tune the BACKWARD kernel of a
# pair separately from its forward (DESIGN.md §13): distill_kl's
# backward is embarrassingly parallel where its forward is a sequential
# vocab sweep, and flash-attention's dq/dkv streams have different
# residency than the forward's online softmax — the same block winner
# rarely serves both directions. ssd_scan has NO ``_bwd`` entry by
# construction: its residual contract snapshots carried states at
# *forward* chunk boundaries, so the backward must walk the identical
# chunk grid (a separate bwd chunk would misalign the snapshots).
KERNEL_BLOCK_ARGS = {
    "distill_kl": ("block_rows", "block_v"),
    "distill_kl_bwd": ("block_rows", "block_v"),
    "flash_attention": ("block_q", "block_k"),
    "flash_attention_bwd": ("block_q", "block_k"),
    "ssd_scan": ("chunk",),
    "paged_attention": ("page",),
}

# per-backend default execution modes. ensemble_shard stays "none" on
# every backend: sharding is a topology choice (how many devices carry
# the client axis), not a backend choice — opt in per-scfg. The same
# reasoning pins the federation-scale knobs (DESIGN.md §13) to their
# bit-compat-off settings on every backend: bucketing/chunking/tree
# reduction are *federation-size* choices (m=1000 wants them, m=10
# must stay bitwise-identical to the unchunked path), so scenarios
# opt in per-scfg rather than inheriting them from the hardware.
_SCALE_DEFAULTS = {"bucketing": "off", "stack_chunk": 0,
                   "fedavg": "flat", "fedavg_branch": 8,
                   "teacher_chunk": 0}
_PROFILES = {
    "cpu": {"loop": "python", "client_loop": "grouped",
            "ensemble_shard": "none", "distill_kl": "ref",
            "kernel_vjp": "ref", "interpret": True, **_SCALE_DEFAULTS},
    "gpu": {"loop": "fused", "client_loop": "grouped",
            "ensemble_shard": "none", "distill_kl": "fused",
            "kernel_vjp": "fused", "interpret": False, **_SCALE_DEFAULTS},
    "tpu": {"loop": "fused", "client_loop": "grouped",
            "ensemble_shard": "none", "distill_kl": "fused",
            "kernel_vjp": "fused", "interpret": False, **_SCALE_DEFAULTS},
}

# per-backend default block shapes. The cpu row reproduces the historical
# hardcoded kwargs exactly; accelerator rows start from the same values
# and are refined by the autotuner cache, not by code edits.
_BLOCKS = {
    "cpu": {"distill_kl": (256, 2048), "distill_kl_bwd": (256, 2048),
            "flash_attention": (128, 128), "flash_attention_bwd": (128, 128),
            "ssd_scan": (128,), "paged_attention": (16,)},
    "gpu": {"distill_kl": (256, 2048), "distill_kl_bwd": (256, 2048),
            "flash_attention": (128, 128), "flash_attention_bwd": (128, 128),
            "ssd_scan": (128,), "paged_attention": (16,)},
    "tpu": {"distill_kl": (256, 1024), "distill_kl_bwd": (256, 1024),
            "flash_attention": (256, 256), "flash_attention_bwd": (256, 256),
            "ssd_scan": (256,), "paged_attention": (128,)},
}

# autotuner candidate block shapes, in canonical order — ties between
# equally-timed candidates break toward the EARLIEST entry, so this
# order is part of the determinism contract. The ``*_bwd`` candidate
# lists mirror the forward's; their thunks time the standalone backward
# kernel (distill_kl_bwd / flash_attention_bwd) on precomputed forward
# residuals, so a bwd winner reflects only backward-stream cost.
_CANDIDATES = {
    "distill_kl": ((256, 2048), (128, 1024), (64, 512), (32, 256)),
    "distill_kl_bwd": ((256, 2048), (128, 1024), (64, 512), (32, 256)),
    "flash_attention": ((128, 128), (64, 64), (32, 32)),
    "flash_attention_bwd": ((128, 128), (64, 64), (32, 32)),
    "ssd_scan": ((128,), (64,), (32,)),
    "paged_attention": ((16,), (32,), (64,)),
}

_SEED_CACHE = os.path.join(os.path.dirname(__file__), "autotune_seed.json")
_CACHE_VERSION = 1


def check_loop_mode(mode):
    if mode not in LOOP_MODES:
        raise ValueError(f"unknown loop_mode {mode!r} "
                         "(expected 'python' or 'fused')")


def check_client_loop_mode(mode):
    if mode not in CLIENT_LOOP_MODES:
        raise ValueError(f"unknown client_loop_mode {mode!r} "
                         "(expected 'python' or 'grouped')")


def check_shard_mode(mode):
    if mode not in SHARD_MODES:
        raise ValueError(f"unknown ensemble_shard_mode {mode!r} "
                         f"(expected one of {SHARD_MODES})")


def check_kl_mode(mode):
    if mode not in KL_MODES:
        raise ValueError(f"unknown distill_kl mode {mode!r} "
                         f"(expected one of {KL_MODES})")


def check_kernel_vjp_mode(mode):
    if mode not in KERNEL_VJP_MODES:
        raise ValueError(f"unknown kernel_vjp mode {mode!r} "
                         f"(expected one of {KERNEL_VJP_MODES})")


def check_bucketing_mode(mode):
    if mode not in BUCKETING_MODES:
        raise ValueError(f"unknown plan_bucketing {mode!r} "
                         f"(expected one of {BUCKETING_MODES})")


def check_fedavg_mode(mode):
    if mode not in FEDAVG_MODES:
        raise ValueError(f"unknown fedavg_mode {mode!r} "
                         f"(expected one of {FEDAVG_MODES})")


def check_chunk_size(name, value):
    """Chunk knobs are non-negative ints; 0 disables chunking."""
    if int(value) != value or int(value) < 0:
        raise ValueError(f"{name} must be a non-negative int, "
                         f"got {value!r}")


def check_fedavg_branch(value):
    if int(value) != value or int(value) < 2:
        raise ValueError(f"fedavg_branch must be an int >= 2, "
                         f"got {value!r}")


def detect_backend(scfg=None) -> str:
    """scfg.backend > REPRO_BACKEND env > jax.default_backend()."""
    b = getattr(scfg, "backend", None)
    if b is None:
        b = os.environ.get("REPRO_BACKEND") or None
    if b is None:
        import jax
        b = jax.default_backend()
    b = str(b).lower()
    if b not in BACKENDS:
        raise ValueError(f"unknown backend {b!r} "
                         f"(expected one of {BACKENDS})")
    return b


# ------------------------------------------------------------ ExecPolicy

@dataclass(frozen=True)
class ExecPolicy:
    """Frozen, hashable resolution of every execution decision.

    Field names are deliberately SHORT (``loop``, not ``loop_mode``):
    the grep-enforcement test bans the long knob names outside configs/,
    and policy reads must not trip it.

    ``blocks`` is the registry default table, ``tuned`` the autotuner
    cache entries for this backend, ``overrides`` explicit per-scfg /
    per-arch choices — ``blocks_for`` applies them in increasing
    precedence. All three are nested tuples so the policy hashes (it is
    used as a jit-static value and as a cache key).
    """
    backend: str = "cpu"
    loop: str = "python"
    client_loop: str = "grouped"
    ensemble_shard: str = "none"
    distill_kl: str = "ref"
    kernel_vjp: str = "ref"
    interpret: bool = True
    # federation-scale knobs (DESIGN.md §13); short names again because
    # the grep test bans the scfg spellings outside configs/
    bucketing: str = "off"
    stack_chunk: int = 0
    fedavg: str = "flat"
    fedavg_branch: int = 8
    teacher_chunk: int = 0
    # ((kernel, (vals...)), ...) in KERNEL_BLOCK_ARGS order
    blocks: tuple = ()
    # (((kernel, bucket), (vals...)), ...) from the autotune cache
    tuned: tuple = ()
    # ((kernel, (val_or_None...)), ...) — explicit choices; None inherits
    overrides: tuple = ()

    def replace(self, **kw) -> "ExecPolicy":
        return dataclasses.replace(self, **kw)

    def blocks_for(self, kernel: str, shape=None) -> tuple:
        """Block shapes for one kernel: explicit overrides beat the
        autotuned cache entry for ``shape``'s bucket, which beats the
        registry default table."""
        names = KERNEL_BLOCK_ARGS[kernel]
        vals = dict(self.blocks).get(kernel, _BLOCKS[self.backend][kernel])
        if shape is not None:
            hit = dict(self.tuned).get((kernel, shape_bucket(kernel, shape)))
            if hit is not None:
                vals = hit
        ov = dict(self.overrides).get(kernel)
        if ov is not None:
            vals = tuple(v if o is None else o for v, o in zip(vals, ov))
        if len(vals) != len(names):
            raise ValueError(f"{kernel} expects {len(names)} block values "
                             f"{names}, got {vals!r}")
        return tuple(int(v) for v in vals)

    def block_kwargs(self, kernel: str, shape=None) -> dict:
        return dict(zip(KERNEL_BLOCK_ARGS[kernel],
                        self.blocks_for(kernel, shape)))

    def override_blocks(self, kernel: str, **named) -> "ExecPolicy":
        """New policy with explicit block choices for one kernel; values
        of None inherit (tuned/registry) per position."""
        names = KERNEL_BLOCK_ARGS[kernel]
        bad = set(named) - set(names)
        if bad:
            raise ValueError(f"unknown block args {sorted(bad)} for "
                             f"{kernel} (expected {names})")
        cur = dict(self.overrides)
        prev = cur.get(kernel, (None,) * len(names))
        cur[kernel] = tuple(named.get(n, p) for n, p in zip(names, prev))
        return self.replace(overrides=tuple(sorted(cur.items())))


def _freeze_blocks(table: dict) -> tuple:
    return tuple(sorted((k, tuple(v)) for k, v in table.items()))


def _normalize_overrides(kernel_blocks) -> tuple:
    """Accept scfg.kernel_blocks as a mapping or tuple of pairs, values
    either positional tuples or name->int mappings."""
    if not kernel_blocks:
        return ()
    items = kernel_blocks.items() if hasattr(kernel_blocks, "items") \
        else kernel_blocks
    out = {}
    for kernel, vals in items:
        names = KERNEL_BLOCK_ARGS.get(kernel)
        if names is None:
            raise ValueError(f"unknown kernel {kernel!r} in kernel_blocks "
                             f"(expected one of {tuple(KERNEL_BLOCK_ARGS)})")
        if hasattr(vals, "items"):
            vals = tuple(vals.get(n) for n in names)
        vals = tuple(vals)
        if len(vals) != len(names):
            raise ValueError(f"kernel_blocks[{kernel!r}] expects "
                             f"{len(names)} values {names}, got {vals!r}")
        out[kernel] = tuple(None if v is None else int(v) for v in vals)
    return tuple(sorted(out.items()))


# ---------------------------------------------------- autotune cache IO

def _default_cache_path() -> str:
    return os.environ.get(
        "REPRO_AUTOTUNE_CACHE",
        os.path.join(os.path.expanduser("~"), ".cache", "repro-dense",
                     "autotune.json"))


def _read_cache_file(path: str) -> dict:
    """{'backend/kernel/bucket': [blocks...]} from one JSON cache file;
    a corrupt or stale-format file degrades to registry defaults with a
    warning instead of failing resolution."""
    if not os.path.exists(path):
        return {}
    try:
        with open(path) as f:
            doc = json.load(f)
        if doc.get("version") != _CACHE_VERSION:
            raise ValueError(f"cache version {doc.get('version')!r} != "
                             f"{_CACHE_VERSION}")
        entries = {}
        for key, ent in doc["entries"].items():
            backend, kernel, bucket = key.split("/")
            names = KERNEL_BLOCK_ARGS[kernel]
            vals = tuple(int(ent["blocks"][n]) for n in names)
            entries[(backend, kernel, bucket)] = vals
        return entries
    except Exception as e:  # noqa: BLE001 — any corruption falls back
        warnings.warn(f"ignoring unreadable autotune cache {path}: {e}; "
                      "falling back to registry default blocks",
                      stacklevel=2)
        return {}


_cache_memo: dict = {}


def _load_cache() -> dict:
    """Seed cache, overlaid by the writable cache only when autotuning is
    on (``REPRO_AUTOTUNE=1``); memoized per (path, mtime) so resolution
    stays cheap at trace time."""
    path = _default_cache_path() if autotune_enabled() else None
    sig = (path, _mtime(_SEED_CACHE), path and _mtime(path))
    if _cache_memo.get("sig") != sig:
        entries = _read_cache_file(_SEED_CACHE)
        if path:
            entries.update(_read_cache_file(path))
        _cache_memo.clear()
        _cache_memo["sig"] = sig
        _cache_memo["entries"] = entries
    return _cache_memo["entries"]


def _mtime(path):
    try:
        return os.stat(path).st_mtime_ns
    except OSError:
        return None


def clear_caches() -> None:
    """Drop memoized cache state (tests; after external cache edits)."""
    _cache_memo.clear()
    _resolve_memo.clear()


def _write_cache_entry(backend, kernel, bucket, vals, timing_us) -> None:
    path = _default_cache_path()
    doc = {"version": _CACHE_VERSION, "entries": {}}
    if os.path.exists(path):
        try:
            with open(path) as f:
                old = json.load(f)
            if old.get("version") == _CACHE_VERSION:
                doc = old
        except Exception:
            pass  # corrupt writable cache: start fresh
    names = KERNEL_BLOCK_ARGS[kernel]
    doc["entries"][f"{backend}/{kernel}/{bucket}"] = {
        "blocks": dict(zip(names, [int(v) for v in vals])),
        "us": float(timing_us)}
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    clear_caches()


# -------------------------------------------------------------- buckets

def _pow2_ceil(n: int) -> int:
    p = 1
    while p < max(int(n), 1):
        p *= 2
    return p


def shape_bucket(kernel: str, shape) -> str:
    """Shape-bucket key: kernels with the same next-pow2 problem dims
    share one autotune entry. ``shape`` is the tuple of tuning-relevant
    dims ((rows, vocab) / (Sq, Sk) / (S,))."""
    return "x".join(str(_pow2_ceil(d)) for d in shape)


# ------------------------------------------------------------ resolution

_resolve_memo: dict = {}


def resolve_exec_policy(scfg=None, *, backend=None) -> "ExecPolicy":
    """THE resolution entrypoint: modes and block shapes for one run.

    ``scfg`` may be a DenseExperimentConfig, any knob-carrying namespace,
    an ExecPolicy (returned unchanged — idempotent), or None (pure
    registry defaults for the detected backend). Per-scfg knobs that are
    present and not None override the registry profile; every mode is
    validated here (same error messages the scattered call-site checks
    used to raise). Output is bit-stable for a fixed (backend, scfg,
    cache) triple: resolution is pure in those inputs and memoized when
    scfg hashes.
    """
    if isinstance(scfg, ExecPolicy):
        return scfg
    b = backend or detect_backend(scfg)
    try:
        key = (b, scfg, os.environ.get("REPRO_INTERPRET"),
               autotune_enabled(), _cache_memo.get("sig"))
        hash(key)
    except TypeError:
        key = None
    if key is not None and key in _resolve_memo:
        return _resolve_memo[key]
    prof = _PROFILES[b]

    def knob(name, default):
        v = getattr(scfg, name, None)
        return default if v is None else v

    loop = knob("loop_mode", prof["loop"])
    client_loop = knob("client_loop_mode", prof["client_loop"])
    shard = knob("ensemble_shard_mode", prof["ensemble_shard"])
    kl = knob("distill_kl_mode", prof["distill_kl"])
    vjp = knob("kernel_vjp_mode", prof["kernel_vjp"])
    bucketing = knob("plan_bucketing", prof["bucketing"])
    stack_chunk = knob("stack_chunk", prof["stack_chunk"])
    favg = knob("fedavg_mode", prof["fedavg"])
    fbranch = knob("fedavg_branch", prof["fedavg_branch"])
    tchunk = knob("teacher_chunk", prof["teacher_chunk"])
    check_loop_mode(loop)
    check_client_loop_mode(client_loop)
    check_shard_mode(shard)
    check_kl_mode(kl)
    check_kernel_vjp_mode(vjp)
    check_bucketing_mode(bucketing)
    check_chunk_size("stack_chunk", stack_chunk)
    check_fedavg_mode(favg)
    check_fedavg_branch(fbranch)
    check_chunk_size("teacher_chunk", tchunk)
    interp = prof["interpret"]
    env_i = os.environ.get("REPRO_INTERPRET")
    if env_i is not None and env_i != "":
        interp = env_i not in ("0", "false", "False")
    cache = _load_cache()
    tuned = tuple(sorted((
        ((kernel, bucket), vals)
        for (cb, kernel, bucket), vals in cache.items() if cb == b)))
    pol = ExecPolicy(
        backend=b, loop=loop, client_loop=client_loop, ensemble_shard=shard,
        distill_kl=kl, kernel_vjp=vjp, interpret=bool(interp),
        bucketing=bucketing, stack_chunk=int(stack_chunk), fedavg=favg,
        fedavg_branch=int(fbranch), teacher_chunk=int(tchunk),
        blocks=_freeze_blocks(_BLOCKS[b]), tuned=tuned,
        overrides=_normalize_overrides(getattr(scfg, "kernel_blocks", ())))
    if key is not None:
        _resolve_memo[key] = pol
    return pol


def arch_policy(cfg) -> "ExecPolicy":
    """Model-layer resolution from an ArchConfig: ``kernel_vjp_mode``
    (when set; None → registry), and the config's tile fields
    (attn_block_q/attn_block_kv, ssm_chunk) as explicit block overrides.
    models/attention.py and models/ssm.py route every kernel decision
    through this."""
    pol = resolve_exec_policy(None)
    vjp = getattr(cfg, "kernel_vjp_mode", None)
    if vjp is not None:
        check_kernel_vjp_mode(vjp)
        pol = pol.replace(kernel_vjp=vjp)
    bq = getattr(cfg, "attn_block_q", None)
    bk = getattr(cfg, "attn_block_kv", None)
    if bq is not None or bk is not None:
        pol = pol.override_blocks("flash_attention", block_q=bq, block_k=bk)
    chunk = getattr(cfg, "ssm_chunk", None)
    if chunk is not None:
        pol = pol.override_blocks("ssd_scan", chunk=chunk)
    return pol


# ------------------------------------------------------------- autotuner

def autotune_enabled() -> bool:
    return os.environ.get("REPRO_AUTOTUNE", "") not in ("", "0")


def _timer(fn, reps: int = 3) -> float:
    """Median wall-clock microseconds of ``fn()`` over ``reps`` calls
    (after one warmup). Monkeypatched by the determinism tests."""
    fn()
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append((time.perf_counter() - t0) * 1e6)
    return sorted(ts)[len(ts) // 2]


def _pick_winner(timings) -> int:
    """Index of the fastest candidate; exact ties break to the EARLIEST
    candidate in canonical _CANDIDATES order (deterministic across
    runs and machines with quantized timers)."""
    return min(range(len(timings)), key=lambda i: (timings[i], i))


def _candidate_runner(kernel, shape, blocks, interpret):
    """A thunk timing one kernel at ``shape`` with candidate ``blocks``
    on synthetic inputs (fresh concrete arrays — never the traced
    operands, so tuning composes with jit tracing). Forward entries time
    the pair's forward; ``*_bwd`` entries time the standalone backward
    kernel on precomputed forward residuals, so the two directions tune
    independently (DESIGN.md §13)."""
    import importlib

    import jax
    import jax.numpy as jnp

    # the public names in repro.kernels shadow the submodules (ops.py
    # wrappers are re-exported as repro.kernels.distill_kl etc.), so the
    # low-level modules must be resolved by full dotted path
    if kernel == "distill_kl_bwd":
        _kl = importlib.import_module("repro.kernels.distill_kl")
        rows, v = shape
        t = jnp.linspace(-1.0, 1.0, rows * v, dtype=jnp.float32)
        t = t.reshape(rows, v)
        s = t[:, ::-1]
        # forward residuals at the forward's registry-default blocks —
        # held fixed so only the backward stream is on the clock
        fbr, fbv = _BLOCKS["cpu"]["distill_kl"]
        klv, (mt, zt, _st, ms, zs) = _kl.distill_kl(
            t, s, block_rows=fbr, block_v=fbv, interpret=interpret,
            return_stats=True)
        lse_t, lse_s = mt + jnp.log(zt), ms + jnp.log(zs)
        g = jnp.ones((rows,), jnp.float32)
        br, bv = blocks

        def run():
            jax.block_until_ready(_kl.distill_kl_bwd(
                t, s, lse_t, lse_s, klv, g, block_rows=br, block_v=bv,
                interpret=interpret))
    elif kernel == "flash_attention_bwd":
        _fa = importlib.import_module("repro.kernels.flash_attention")
        sq, sk = shape
        d = 16
        q = jnp.linspace(-1.0, 1.0, sq * d,
                         dtype=jnp.float32).reshape(1, 1, sq, d)
        k = jnp.linspace(-1.0, 1.0, sk * d,
                         dtype=jnp.float32).reshape(1, 1, sk, d)
        fbq, fbk = _BLOCKS["cpu"]["flash_attention"]
        _out, o_f32, lse = _fa.flash_attention(
            q, k, k, causal=True, window=0, block_q=fbq, block_k=fbk,
            interpret=interpret, return_stats=True)
        g = jnp.ones_like(q)
        bq, bk = blocks

        def run():
            jax.block_until_ready(_fa.flash_attention_bwd(
                q, k, k, o_f32, lse, g, causal=True, window=0, scale=None,
                block_q=bq, block_k=bk, interpret=interpret))
    elif kernel == "distill_kl":
        _kl = importlib.import_module("repro.kernels.distill_kl")
        rows, v = shape
        t = jnp.linspace(-1.0, 1.0, rows * v, dtype=jnp.float32)
        t = t.reshape(rows, v)
        s = t[:, ::-1]
        br, bv = blocks

        def run():
            jax.block_until_ready(_kl.distill_kl_vjp(t, s, br, bv,
                                                     interpret, False))
    elif kernel == "flash_attention":
        _fa = importlib.import_module("repro.kernels.flash_attention")
        sq, sk = shape
        d = 16
        q = jnp.linspace(-1.0, 1.0, sq * d,
                         dtype=jnp.float32).reshape(1, 1, sq, d)
        k = jnp.linspace(-1.0, 1.0, sk * d,
                         dtype=jnp.float32).reshape(1, 1, sk, d)
        bq, bk = blocks

        def run():
            jax.block_until_ready(_fa.flash_attention(
                q, k, k, causal=True, window=0, block_q=bq, block_k=bk,
                interpret=interpret))
    elif kernel == "paged_attention":
        _pa = importlib.import_module("repro.kernels.paged_attention")
        # shape = (max_len,): page candidates trade gather granularity
        # against per-block overhead at the engine's sequence capacity
        (t,) = shape
        (page,) = blocks
        r, d = 2, 16
        m = max(1, -(-int(t) // page))
        pool = jnp.linspace(-1.0, 1.0, (r * m + 1) * page * d,
                            dtype=jnp.float32).reshape(r * m + 1, 1, page, d)
        q = jnp.linspace(-1.0, 1.0, r * d,
                         dtype=jnp.float32).reshape(r, 1, d)
        bt = jnp.arange(r * m, dtype=jnp.int32).reshape(r, m) + 1
        seq = jnp.full((r,), int(t), jnp.int32)

        def run():
            jax.block_until_ready(_pa.paged_attention(
                q, pool, pool, bt, seq, interpret=interpret))
    elif kernel == "ssd_scan":
        _ssd = importlib.import_module("repro.kernels.ssd_scan")
        (s,) = shape
        h, p, n = 1, 4, 4
        x = jnp.linspace(-1.0, 1.0, s * h * p,
                         dtype=jnp.float32).reshape(1, s, h, p)
        dt = jnp.full((1, s, h), 0.1, jnp.float32)
        a = -jnp.ones((h,), jnp.float32)
        bmat = jnp.linspace(-1.0, 1.0, s * h * n,
                            dtype=jnp.float32).reshape(1, s, h, n)
        (chunk,) = blocks

        def run():
            jax.block_until_ready(_ssd.ssd_scan(
                x, dt, a, bmat, bmat, chunk=chunk, interpret=interpret))
    else:
        raise ValueError(f"unknown kernel {kernel!r}")
    return run


def autotune_blocks(kernel: str, shape, policy: "ExecPolicy") -> tuple:
    """Block shapes for ``(policy.backend, kernel, bucket(shape))``.

    Cache hit (seed or writable) returns immediately — no timing. On a
    miss with ``REPRO_AUTOTUNE=1`` each candidate (clamped into the
    problem shape and deduplicated, keeping canonical order) is timed
    and the deterministic winner is persisted to the writable cache;
    with autotuning off the registry default is returned untimed.
    """
    bucket = shape_bucket(kernel, shape)
    cached = _load_cache().get((policy.backend, kernel, bucket))
    if cached is not None:
        return cached
    if not autotune_enabled():
        return policy.blocks_for(kernel)
    cands, seen = [], set()
    for cand in _CANDIDATES[kernel]:
        clamped = tuple(min(int(c), _pow2_ceil(d))
                        for c, d in zip(cand, shape))
        if clamped not in seen:
            seen.add(clamped)
            cands.append(clamped)
    timings = [_timer(_candidate_runner(kernel, tuple(int(d) for d in shape),
                                        c, policy.interpret))
               for c in cands]
    win = _pick_winner(timings)
    _write_cache_entry(policy.backend, kernel, bucket, cands[win],
                       timings[win])
    return cands[win]


__all__ = [
    "BACKENDS", "LOOP_MODES", "CLIENT_LOOP_MODES", "SHARD_MODES",
    "KL_MODES", "KERNEL_VJP_MODES", "BUCKETING_MODES", "FEDAVG_MODES",
    "KERNEL_BLOCK_ARGS", "ExecPolicy",
    "detect_backend", "resolve_exec_policy", "arch_policy",
    "shape_bucket", "autotune_blocks", "autotune_enabled", "clear_caches",
    "check_loop_mode", "check_client_loop_mode", "check_shard_mode",
    "check_kl_mode", "check_kernel_vjp_mode", "check_bucketing_mode",
    "check_fedavg_mode", "check_chunk_size", "check_fedavg_branch",
]
