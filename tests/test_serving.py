"""Serving engine tests (DESIGN.md §12).

The contract under test: the paged block-pool engine with continuous
batching produces EXACTLY the tokens of the sequential batch-1
dense-cache reference, request by request, whatever shares its decode
batch — across attention (llama), pure-SSM (mamba2) and hybrid (zamba2)
families, under slot recycling, pool exhaustion and mid-flight arrivals.
Plus the paged decode-attention kernel vs its oracle over ragged
block-table tails, and the block allocator's invariants.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import backend as B
from repro.configs.base import get_smoke_config
from repro.kernels import paged_attention as PK
from repro.kernels import ref
from repro.launch import paging as PG
from repro.launch.engine import ServeEngine, engine_keys
from repro.launch.serve import serve
from repro.models import transformer as T

ARCHS = ["llama3.2-3b", "mamba2-130m", "zamba2-7b"]

# ragged on purpose: three distinct prompt lengths AND gen budgets, so
# requests start and finish at different scheduler iterations
_PROMPTS = [(5, 6), (9, 4), (12, 7)]          # (prompt_len, max_new)


def _mk(arch, seed=0):
    cfg = get_smoke_config(arch)
    k_init, k_prompt, _ = engine_keys(seed)
    params = T.init_model(k_init, cfg)
    prompts = [np.asarray(jax.random.randint(
        jax.random.fold_in(k_prompt, i), (p,), 0, cfg.vocab_size), np.int32)
        for i, (p, _) in enumerate(_PROMPTS)]
    return cfg, params, prompts


def _run(cfg, params, prompts, mode, *, max_reqs=2, seed=0, sampling=None,
         **kw):
    eng = ServeEngine(cfg, params, mode=mode, max_reqs=max_reqs,
                      max_len=max(p + g for p, g in _PROMPTS), seed=seed,
                      **kw)
    sampling = sampling or [None] * len(prompts)
    rids = [eng.submit(pr, max_new=g, sampling=s)
            for pr, (_, g), s in zip(prompts, _PROMPTS, sampling)]
    out = eng.drain()
    return [out[r] for r in rids], eng


# -------------------------------------------- paged ≡ dense, per family --

@pytest.mark.parametrize("arch", ARCHS)
def test_paged_equals_dense(arch):
    """Continuous paged decode == sequential dense reference, token for
    token, with ragged prompts and 3 requests sharing 2 slots (so the
    third request recycles a freed slot + released blocks)."""
    cfg, params, prompts = _mk(arch)
    dense, _ = _run(cfg, params, prompts, "dense")
    paged, eng = _run(cfg, params, prompts, "paged", max_reqs=2)
    for d, p in zip(dense, paged):
        np.testing.assert_array_equal(d, p)
    # every block returned to the pool after drain
    assert eng.allocator.n_free == eng.allocator.n_blocks - 1


def test_paged_kernel_path_end_to_end():
    """Same equivalence with the engine's ops.paged_attention routed to
    the Pallas kernel (cfg.kernel_vjp_mode='autodiff'; the CPU profile's
    interpret=True rides along) instead of the ref oracle."""
    cfg, params, prompts = _mk("llama3.2-3b")
    kcfg = cfg.replace(kernel_vjp_mode="autodiff")
    dense, _ = _run(cfg, params, prompts, "dense")
    paged, _ = _run(kcfg, params, prompts, "paged")
    for d, p in zip(dense, paged):
        np.testing.assert_array_equal(d, p)


# --------------------------------------- continuous ≡ sequential arrivals --

def test_continuous_equals_sequential_under_arrival_trace():
    """Fixed arrival trace: requests join a RUNNING decode batch at
    different steps (one of them temperature-sampled). Per-request token
    streams must equal the submit-everything-upfront sequential dense
    run — sampling is keyed by (rid, token_index), never by batch
    composition."""
    cfg, params, prompts = _mk("llama3.2-3b", seed=3)
    sampling = [None, {"temperature": 0.7}, None]

    seq, _ = _run(cfg, params, prompts, "dense", seed=3, sampling=sampling)

    eng = ServeEngine(cfg, params, mode="paged", max_reqs=3,
                      max_len=max(p + g for p, g in _PROMPTS), seed=3)
    r0 = eng.submit(prompts[0], max_new=_PROMPTS[0][1])
    eng.step(); eng.step()                       # r0 decoding alone
    r1 = eng.submit(prompts[1], max_new=_PROMPTS[1][1],
                    sampling=sampling[1])
    eng.step()                                   # r1 joins mid-flight
    r2 = eng.submit(prompts[2], max_new=_PROMPTS[2][1])
    out = eng.drain()
    for want, got in zip(seq, (out[r0], out[r1], out[r2])):
        np.testing.assert_array_equal(want, got)


# ----------------------------------------- pool exhaustion and recycling --

def test_pool_exhaustion_queues_then_recycles():
    """A pool sized for ONE worst-case request forces fully sequential
    admission: later submits queue (FIFO), each admission reuses the
    blocks the previous request released — and the tokens still match
    the roomy-pool run."""
    cfg, params, prompts = _mk("mamba2-130m")
    roomy, _ = _run(cfg, params, prompts, "paged", max_reqs=3)

    max_len = max(p + g for p, g in _PROMPTS)
    eng = ServeEngine(cfg, params, mode="paged", max_reqs=3,
                      max_len=max_len, page=4,
                      n_blocks=1 + PG.blocks_needed(max_len, 0, 4))
    rids = [eng.submit(pr, max_new=g)
            for pr, (_, g) in zip(prompts, _PROMPTS)]
    running_high = 0
    while any(eng.poll(r)["status"] != "done" for r in rids):
        eng.step()
        running_high = max(running_high, sum(
            1 for r in rids if eng.poll(r)["status"] == "running"))
    assert running_high == 1                     # never two in flight
    assert eng.allocator.n_free == eng.allocator.n_blocks - 1
    for want, r in zip(roomy, rids):
        np.testing.assert_array_equal(want, eng.poll(r)["tokens"])


def test_impossible_request_raises_not_hangs():
    """A request whose block budget exceeds the WHOLE pool can never be
    admitted — step() must raise (deadlock detection), not spin."""
    cfg, params, prompts = _mk("llama3.2-3b")
    eng = ServeEngine(cfg, params, mode="paged", max_reqs=2, max_len=32,
                      page=4, n_blocks=3)        # pool: 2 usable blocks
    eng.submit(prompts[0], max_new=12)           # needs 5 > 2 blocks
    with pytest.raises(RuntimeError, match="pool too small"):
        eng.step()


def test_submit_validation_and_poll_lifecycle():
    cfg, params, prompts = _mk("llama3.2-3b")
    eng = ServeEngine(cfg, params, mode="paged", max_reqs=2, max_len=16)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(np.zeros((0,), np.int32))
    with pytest.raises(ValueError, match="max_new"):
        eng.submit(prompts[0], max_new=0)
    with pytest.raises(ValueError, match="exceeds"):
        eng.submit(prompts[0], max_new=12)       # 5 + 12 > 16
    rid = eng.submit(prompts[0], max_new=2)
    assert eng.poll(rid)["status"] == "queued"
    eng.drain()
    done = eng.poll(rid)
    assert done["status"] == "done" and len(done["tokens"]) == 2
    assert done["latency_s"] >= 0.0


def test_block_allocator_invariants():
    a = PG.BlockAllocator(5)                     # blocks 1..4 usable
    assert a.n_free == 4
    got = a.alloc(3)
    assert got is not None and 0 not in got and len(set(got)) == 3
    assert a.alloc(2) is None and a.n_free == 1  # all-or-nothing
    a.release(got)
    assert a.n_free == 4
    with pytest.raises(ValueError, match="double free"):
        a.release(got)
    with pytest.raises(ValueError, match=">= 2"):
        PG.BlockAllocator(1)


def test_unsupported_family_falls_back_to_dense():
    """Sliding-window dense layouts aren't paged: mode auto-selects the
    sequential fallback, and forcing paged fails fast."""
    cfg, params, _ = _mk("llama3.2-3b")
    swcfg = cfg.replace(sliding_window=8)
    assert not PG.supports_paged(swcfg)
    eng = ServeEngine(swcfg, params, max_reqs=1, max_len=16)
    assert eng.mode == "dense"
    with pytest.raises(ValueError, match="paged mode unsupported"):
        ServeEngine(swcfg, params, mode="paged", max_reqs=1, max_len=16)


# --------------------------------- paged kernel vs oracle, ragged tails --

@pytest.mark.parametrize("page,m,seqs", [
    (8, 4, (1, 17, 32)),       # one token / mid-block tail / full table
    (8, 4, (8, 16, 24)),       # exact block boundaries
    (16, 2, (3, 31, 32)),
    (4, 7, (5, 13, 27)),       # odd page count, ragged everywhere
])
def test_paged_kernel_matches_oracle_ragged(page, m, seqs):
    """kernels.paged_attention (interpret) vs kernels.ref oracle across
    ragged block-table tails, GQA grouping included."""
    r, hq, hkv, d = len(seqs), 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(17), 3)
    n_blocks = 1 + r * m
    q = jax.random.normal(ks[0], (r, hq, d), jnp.float32)
    kp = jax.random.normal(ks[1], (n_blocks, hkv, page, d), jnp.float32)
    vp = jax.random.normal(ks[2], (n_blocks, hkv, page, d), jnp.float32)
    bt = (jnp.arange(r * m, dtype=jnp.int32) + 1).reshape(r, m)
    seq = jnp.asarray(seqs, jnp.int32)
    out = PK.paged_attention(q, kp, vp, bt, seq, interpret=True)
    want = ref.paged_attention(q, kp, vp, bt, seq)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5)


def test_paged_kernel_null_row_is_zero_mass():
    """A seq_len of 0 (inactive scheduler slot pointing at block 0) must
    contribute exactly zero output — the masked p never touches pool
    garbage."""
    page, m = 8, 2
    q = jnp.ones((2, 2, 8), jnp.float32)
    pool = jnp.full((5, 1, page, 8), 7.5, jnp.float32)
    bt = jnp.asarray([[0, 0], [1, 2]], jnp.int32)
    seq = jnp.asarray([0, 5], jnp.int32)
    out = PK.paged_attention(q, pool, pool, bt, seq, interpret=True)
    np.testing.assert_array_equal(np.asarray(out[0]), 0.0)
    np.testing.assert_allclose(np.asarray(out[1]), 7.5, atol=1e-5)


def test_ops_paged_attention_policy_routing():
    """ops.paged_attention honors kernel_vjp='ref' (oracle) vs kernel
    routing and rejects unknown modes — same registry contract as the
    other kernels."""
    from repro.kernels import ops
    pol = B.resolve_exec_policy(None)
    q = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 16))
    pool = jax.random.normal(jax.random.PRNGKey(2), (5, 2, 8, 16))
    bt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    seq = jnp.asarray([5, 11], jnp.int32)
    a = ops.paged_attention(q, pool, pool, bt, seq,
                            policy=pol.replace(kernel_vjp="ref"))
    b = ops.paged_attention(
        q, pool, pool, bt, seq,
        policy=pol.replace(kernel_vjp="autodiff", interpret=True))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    with pytest.raises(ValueError, match="unknown kernel_vjp mode"):
        ops.paged_attention(q, pool, pool, bt, seq,
                            policy=pol.replace(kernel_vjp="bogus"))


# ------------------------------------------------- serve() compat wrapper --

def test_serve_wrapper_compat_paged_equals_dense():
    """The thin serve() wrapper keeps the historical (tokens, stats)
    contract, and its paged/dense modes agree."""
    toks_p, stats_p = serve("llama3.2-3b", batch=2, prompt_len=8, gen=4,
                            smoke=True, mode="paged")
    toks_d, stats_d = serve("llama3.2-3b", batch=2, prompt_len=8, gen=4,
                            smoke=True, mode="dense")
    assert toks_p.shape == (2, 4) and toks_p.dtype == np.int32
    np.testing.assert_array_equal(toks_p, toks_d)
    for st in (stats_p, stats_d):
        assert set(st) >= {"prefill_s", "decode_s", "tok_per_s"}
        assert st["tok_per_s"] > 0
