"""Pure-jnp oracles for every Pallas kernel (the `ref.py` contract).

Tests sweep shapes/dtypes and assert_allclose kernel-vs-oracle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def hash_mm_ref(x: Array, alpha: Array, b: Array, r: float) -> Array:
    proj = x.astype(jnp.float32) @ alpha.astype(jnp.float32)
    return jnp.floor(proj / r + b.astype(jnp.float32)).astype(jnp.int32)


def simhash_pack_ref(x: Array, alpha: Array) -> Array:
    bits = (x.astype(jnp.float32) @ alpha.astype(jnp.float32) >= 0).astype(jnp.int32)
    k = bits.shape[-1]
    words = bits.reshape(bits.shape[:-1] + (k // 32, 32))
    shifts = jnp.arange(32, dtype=jnp.int32)
    return (words << shifts).sum(axis=-1).astype(jnp.int32)


def dct_mm_ref(fvals: Array, dct_t: Array, scale: Array) -> Array:
    return (fvals.astype(jnp.float32) @ dct_t.astype(jnp.float32)) * scale


def rerank_ref(q: Array, emb: Array, ids: Array, p: float = 2.0) -> Array:
    diff = emb.astype(jnp.float32) - q.astype(jnp.float32)[:, None, :]
    if p == 2.0:
        d = jnp.sqrt(jnp.sum(diff * diff, axis=-1))
    elif p == 1.0:
        d = jnp.sum(jnp.abs(diff), axis=-1)
    else:
        d = jnp.sum(jnp.abs(diff) ** p, axis=-1) ** (1.0 / p)
    return jnp.where(ids < 0, jnp.inf, d)


def hash_mm_proj_ref(x: Array, alpha: Array, b: Array, r: float
                     ) -> tuple[Array, Array]:
    """(floor-hashes int32, pre-floor projections f32) -- multi-probe needs
    both; identical arithmetic to hash_mm_ref so hashes agree bitwise."""
    proj = (x.astype(jnp.float32) @ alpha.astype(jnp.float32)) / r \
        + b.astype(jnp.float32)
    return jnp.floor(proj).astype(jnp.int32), proj


def fused_query_topk_ref(q: Array, db: Array, ids: Array, k: int,
                         p: float = 2.0, valid_items=None
                         ) -> tuple[Array, Array]:
    """Oracle for kernels/fused_query: HBM gather + rerank + lax.top_k.

    This IS the memory-bound path the fused kernel exists to kill: the
    gather materializes (nq, C, N) before any arithmetic happens.
    """
    m = db.shape[0]
    emb = db[jnp.clip(ids, 0, m - 1)]                    # (nq, C, N) in HBM
    d = rerank_ref(q, emb, ids, p)
    if valid_items is not None:
        d = jnp.where(ids >= valid_items, jnp.inf, d)
    neg, idx = jax.lax.top_k(-d, k)
    out_ids = jnp.take_along_axis(ids, idx, axis=-1)
    dist = -neg
    return dist, jnp.where(jnp.isinf(dist), -1, out_ids)


# Above this many first-seen table elements (nq * n_cap) the dedup falls
# back to the O(C log C) sort: the table costs nq * n_cap * 4 bytes of HBM
# (2**26 elements = 256 MB).
DEDUP_SCATTER_MAX_ELEMS = 1 << 26


def dedup_candidates(ids: Array, n_cap: int, live: Array | None = None
                     ) -> Array:
    """Mark repeated candidate ids as -1 (the first occurrence in each
    query's row survives), and with ``live`` ((n_cap,) bool) the dead ones
    too.

    ids: (nq, C) int32 ids into ``n_cap`` rows, -1 = empty slot.  Scatter-
    min each id's position into an (nq, n_cap) first-seen table and keep a
    slot iff it scattered first: O(C) work and exact.  A dead item's entry
    starts below every position, so none of its slots is kept: the
    tombstone filter rides on the same gather.  Past
    ``DEDUP_SCATTER_MAX_ELEMS`` table elements a sort keeps each id once
    instead (the same survivors, in id order).

    The block kernel of ``fused_query`` keeps exactly these survivors in
    its candidate walk (``dedup=True``); this table serves the paths it
    does not: the tile kernel, the reference backend and
    ``core.index.gather_stage``.
    """
    nq, c = ids.shape
    if nq * n_cap > DEDUP_SCATTER_MAX_ELEMS:
        cs = jnp.sort(ids, axis=-1)
        dup = jnp.concatenate([jnp.zeros_like(cs[:, :1], dtype=bool),
                               cs[:, 1:] == cs[:, :-1]], axis=-1)
        cs = jnp.where(dup, -1, cs)
        if live is not None:
            cs = jnp.where((cs >= 0) & live[jnp.clip(cs, 0, n_cap - 1)],
                           cs, -1)
        return cs

    rows = jnp.arange(nq)[:, None]
    pos = jnp.arange(c, dtype=jnp.int32)
    # -1 slots must not scatter: negative indices WRAP in jnp.at, so send
    # them to n_cap where mode="drop" discards them.
    scat = jnp.where(ids >= 0, ids, n_cap)
    unseen = (jnp.full((n_cap,), c, jnp.int32) if live is None
              else jnp.where(live, c, -1).astype(jnp.int32))
    first = jnp.broadcast_to(unseen, (nq, n_cap)).at[rows, scat].min(
        pos, mode="drop")
    seen_at = jnp.take_along_axis(first, jnp.clip(ids, 0, n_cap - 1), axis=1)
    keep = (ids >= 0) & (seen_at == pos)
    return jnp.where(keep, ids, -1)
