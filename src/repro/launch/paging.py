"""Block-pool KV cache + per-request SSM state slots (DESIGN.md §12).

``T.init_cache`` allocates one dense ``(batch, prompt+gen)`` cache per
fixed request batch — the serving engine instead draws from a shared
pool sized once at startup:

  * **KV pool** — per attention layer stack, ``(L, P, Kh, page, Dh)``:
    ``P`` fixed-size blocks of ``page`` tokens each, head-major so the
    decode kernel fetches one (page, Dh) tile per (block, kv head).
    Position ``t`` of the request in scheduler slot ``r`` lives at
    ``(block_tables[r, t // page], :, t % page)``.
  * **block tables** — ``(max_reqs, M)`` int32, ``M = ceil(max_len /
    page)``; unassigned entries stay 0.
  * **SSM slots** — mamba2 decode state is O(1) per request, so it is
    slot-indexed rather than paged: the dense state tree with
    ``batch = max_reqs`` (PR 5's ``initial_state`` split≡full fix is
    what makes handing a prefill's final state into slot ``r`` exact).
  * **free list** — host-side LIFO (``BlockAllocator``). **Block 0 is
    reserved** as the null/garbage sink: inactive scheduler slots keep
    all-zero block-table rows, so their (masked-out) decode writes land
    in block 0 instead of corrupting live requests.

Prefill stays dense: a request runs the ordinary exact-length
``T.forward`` prefill, then ``scatter_prefill`` copies the filled dense
cache into its allocated blocks / state slot — the paged layout only
ever serves decode reads (kernels/paged_attention.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs import backend as B
from repro.models import ssm as S

PAGED_FAMILIES = ("dense", "audio", "ssm", "hybrid")


def supports_paged(cfg) -> bool:
    """Families the paged decode path covers. moe (MLA latent cache),
    vlm (cross-attention stream) and sliding-window dense patterns fall
    back to the engine's sequential dense mode."""
    return (cfg.family in PAGED_FAMILIES and not cfg.sliding_window
            and not cfg.kv_lora_rank)


def page_size(policy=None, max_len: int | None = None) -> int:
    """The pool's page size — a cache *layout* choice owned by the
    execution-policy registry (``KERNEL_BLOCK_ARGS["paged_attention"]``),
    resolved once at pool allocation. ``max_len`` is the autotune shape
    bucket (the engine's per-request capacity) and the clamp bound."""
    pol = B.resolve_exec_policy(policy)
    if max_len is not None and B.autotune_enabled():
        (page,) = B.autotune_blocks("paged_attention", (int(max_len),), pol)
    else:
        (page,) = pol.blocks_for("paged_attention")
    if max_len is not None:
        page = min(int(page), int(max_len))
    return max(1, int(page))


def blocks_needed(prompt_len: int, max_new: int, page: int) -> int:
    """Pool blocks a request holds for its whole lifetime (allocated at
    admission — decode never allocates, so it can never deadlock
    mid-flight)."""
    return -(-(int(prompt_len) + int(max_new)) // int(page))


class BlockAllocator:
    """Host-side free-list allocator over pool blocks 1..n_blocks-1
    (block 0 is the reserved null sink and is never handed out)."""

    def __init__(self, n_blocks: int):
        if n_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is reserved)")
        self.n_blocks = int(n_blocks)
        self._free = list(range(self.n_blocks - 1, 0, -1))
        self._used: set[int] = set()

    @property
    def n_free(self) -> int:
        return len(self._free)

    def alloc(self, n: int):
        """``n`` block ids, or None if the pool can't cover the request
        (all-or-nothing: a partial grant could deadlock two admissions)."""
        if n > len(self._free):
            return None
        ids = [self._free.pop() for _ in range(n)]
        self._used.update(ids)
        return ids

    def release(self, ids):
        for i in ids:
            if i not in self._used:
                raise ValueError(f"double free of block {i}")
            self._used.remove(i)
            self._free.append(i)


# ------------------------------------------------------------- pool init --

def init_paged_cache(cfg, *, max_reqs: int, n_blocks: int, page: int):
    """The pool tree. Mirrors ``T.init_cache``'s per-family structure,
    with every attention cache's dense ``(B, T, Kh, Dh)`` axes replaced
    by pool ``(P, Kh, page, Dh)`` axes and every SSM state's batch axis sized
    to ``max_reqs`` slots. Zeros throughout — so unwritten pool rows are
    finite and the kernel's masked lanes multiply against real numbers.
    """
    if not supports_paged(cfg):
        raise ValueError(f"no paged cache layout for family "
                         f"{cfg.family!r} (sliding_window="
                         f"{cfg.sliding_window}, kv_lora_rank="
                         f"{cfg.kv_lora_rank}) — use the sequential "
                         "dense engine mode")
    dtype = jnp.dtype(cfg.dtype)
    fam = cfg.family

    def kv_pool(n):
        shape = (n, n_blocks, cfg.n_kv_heads, page, cfg.head_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    def ssm_slots(lead):
        one = S.mamba2_state_init(cfg, max_reqs, dtype)
        return jax.tree.map(
            lambda a: jnp.zeros((*lead, *a.shape), a.dtype), one)

    if fam in ("dense", "audio"):
        return {"layers": kv_pool(cfg.n_layers)}
    if fam == "ssm":
        return {"layers": ssm_slots((cfg.n_layers,))}
    # hybrid: per-layer mamba2 slots + the shared attention block's pools
    n_super = cfg.n_layers // cfg.attn_every
    tail = cfg.n_layers % cfg.attn_every
    c = {"layers": ssm_slots((n_super, cfg.attn_every)),
         "shared": kv_pool(n_super)}
    if tail:
        c["tail"] = ssm_slots((tail,))
    return c


# -------------------------------------------------------- prefill scatter --

def _scatter_kv(pool, cache, row):
    """Dense prefill KV ``(L, 1, p, Kh, Dh)`` -> pool blocks ``row[:nb]``
    of ``(L, P, Kh, page, Dh)`` (tail of the last block left as zeros)."""
    page = pool["k"].shape[3]
    p = cache["k"].shape[2]
    nb = -(-p // page)
    pad = nb * page - p
    out = {}
    for n in ("k", "v"):
        c = cache[n][:, 0]                              # (L, p, Kh, Dh)
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c = c.reshape(c.shape[0], nb, page, *c.shape[2:])
        c = jnp.swapaxes(c, 2, 3)                       # (L, nb, Kh, page, Dh)
        out[n] = pool[n].at[:, row[:nb]].set(c.astype(pool[n].dtype))
    return out


def _scatter_slot(slots, cache, slot, *, lead: int = 1):
    """Batch-1 SSM state tree -> slot ``slot`` of the slot-indexed tree
    (``lead`` leading stack axes before the batch axis)."""
    def put(sl, c):
        pre = (slice(None),) * lead
        return sl.at[pre + (slot,)].set(c[pre + (0,)].astype(sl.dtype))
    return jax.tree.map(put, slots, cache)


def scatter_prefill(cfg, pools, block_tables, filled, slot, row):
    """Install one admitted request: copy its filled exact-length dense
    prefill cache (``T.init_cache(cfg, 1, p)`` after ``T.forward``) into
    the pool/slots and point block-table row ``slot`` at ``row`` (the
    allocated block ids, zero-padded to M). Traced-safe: ``slot`` and
    ``row`` may be tracers; shapes (p, M) are static per jit cache entry.
    Returns ``(pools, block_tables)``."""
    fam = cfg.family
    if fam in ("dense", "audio"):
        pools = {"layers": _scatter_kv(pools["layers"], filled["layers"],
                                       row)}
    elif fam == "ssm":
        pools = {"layers": _scatter_slot(pools["layers"], filled["layers"],
                                         slot)}
    elif fam == "hybrid":
        new = {"layers": _scatter_slot(pools["layers"], filled["layers"],
                                       slot, lead=2),
               "shared": _scatter_kv(pools["shared"], filled["shared"],
                                     row)}
        if "tail" in pools:
            new["tail"] = _scatter_slot(pools["tail"], filled["tail"], slot)
        pools = new
    else:
        raise ValueError(fam)
    block_tables = block_tables.at[slot].set(row)
    return pools, block_tables
