"""Public wrappers around the Pallas kernels, routed through kernels/dispatch.

Each public function is a thin Python shim that resolves the execution mode
("compiled" Mosaic on TPU / "interpret" on CPU / pure-jnp "reference") and
per-shape block sizes *before* jit, then calls a jit'd implementation with
those choices baked in as static arguments.  Resolving pre-jit keeps the
``REPRO_KERNEL_BACKEND`` env override effective even though jit caches
aggressively: a changed override produces different static args and hence a
fresh trace, never a stale one.

``use_kernel=False`` is the legacy escape hatch (equivalent to
``backend="reference"``) and is kept for callers/tests that predate dispatch.

Shape conventions (shared by every op here): ``B``/``nq`` batch rows, ``N``
embedding dims, ``L`` tables, ``K`` hashes per table, ``C`` candidates per
query, ``k`` results per query.  Serving callers only ever pass the padded
palette shapes -- see docs/architecture.md § "The padded-chunk shape
palette" for the closed set and the knobs that pick it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import dispatch, merge as merge_kernel, quantize, ref
from .dct_mm import dct_mm
from .fused_query import _KP as _FUSED_TOPK_WIDTH
from .fused_query import _resident as _fused_resident
from .fused_query import fused_query_topk as _fused_query_kernel_call
from .hash_mm import hash_mm
from .rerank import rerank_distances
from .simhash_pack import simhash_pack


def _interp(mode: str) -> bool:
    return mode != "compiled"


# -- p-stable hashing --------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("r", "mode", "blocks"))
def _pstable_hash_impl(x, alpha, b, r, mode, blocks):
    if mode == "reference":
        return ref.hash_mm_ref(x, alpha, b, r)
    bm, bn, bk = blocks
    return hash_mm(x, alpha, b, r, bm=bm, bk=bk, bn=bn, interpret=_interp(mode))


def pstable_hash(x, alpha, b, r: float, use_kernel: bool = True,
                 backend: str | None = None):
    """p-stable hash values ``floor((x @ alpha) / r + b)`` -- Eq. (5).

    Args:
        x: (B, N) f32 embeddings.
        alpha: (N, L*K) p-stable projection directions.
        b: (L*K,) uniform offsets in [0, 1).
        r: quantisation width (static; larger r = coarser buckets).
        use_kernel / backend: execution mode, see :mod:`.dispatch`.

    Returns:
        (B, L*K) int32 hash values (callers reshape to (B, L, K)).
    """
    mode = dispatch.kernel_mode(backend, use_kernel)
    blocks = dispatch.matmul_blocks(x.shape[0], x.shape[1], alpha.shape[1])
    return _pstable_hash_impl(x, alpha, b, r, mode, blocks)


@functools.partial(jax.jit, static_argnames=("r", "mode", "blocks"))
def _pstable_hash_proj_impl(x, alpha, b, r, mode, blocks):
    if mode == "reference":
        return ref.hash_mm_proj_ref(x, alpha, b, r)
    bm, bn, bk = blocks
    return hash_mm(x, alpha, b, r, bm=bm, bk=bk, bn=bn,
                   interpret=_interp(mode), return_proj=True)


def pstable_hash_proj(x, alpha, b, r: float, use_kernel: bool = True,
                      backend: str | None = None):
    """Hashes plus the pre-floor projections -- the multi-probe pair.

    Same args as :func:`pstable_hash`.  Returns ``(hashes, proj)``, both
    (B, L*K): ``hashes`` int32 as above, ``proj`` f32 = (x@alpha)/r + b
    before the floor -- its fractional part is each coordinate's distance
    to the bucket boundary, which ranks multi-probe perturbations.
    """
    mode = dispatch.kernel_mode(backend, use_kernel)
    blocks = dispatch.matmul_blocks(x.shape[0], x.shape[1], alpha.shape[1])
    return _pstable_hash_proj_impl(x, alpha, b, r, mode, blocks)


# -- simhash -----------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("mode",))
def _simhash_impl(x, alpha, mode):
    if mode == "reference":
        return ref.simhash_pack_ref(x, alpha)
    return simhash_pack(x, alpha, interpret=_interp(mode))


def simhash_signature(x, alpha, use_kernel: bool = True,
                      backend: str | None = None):
    """Sign-random-projection signature, bit-packed.

    Args:
        x: (B, N) f32 embeddings.
        alpha: (N, K) projection directions, K a multiple of 32.

    Returns:
        (B, K/32) int32 -- bit j of word w is sign(x @ alpha[:, 32w+j]) > 0.
    """
    return _simhash_impl(x, alpha, dispatch.kernel_mode(backend, use_kernel))


# -- Chebyshev / DCT embedding ----------------------------------------------


@functools.partial(jax.jit, static_argnames=("mode",))
def _cheb_impl(fvals, dct_t, scale, mode):
    if mode == "reference":
        return ref.dct_mm_ref(fvals, dct_t, scale)
    return dct_mm(fvals, dct_t, scale, interpret=_interp(mode))


def cheb_embed(fvals, dct_t, scale, use_kernel: bool = True,
               backend: str | None = None):
    """Fused DCT + orthonormal scaling (the Sec. 3.1 embedding's hot path).

    Args:
        fvals: (B, N) function values at the N Chebyshev nodes.
        dct_t: (N, N) DCT-II matrix (transposed).
        scale: (N,) orthonormalisation weights.

    Returns:
        (B, N) f32 scaled Chebyshev coefficients -- the R^N embedding whose
        l^2 distance approximates the functions' L^2 distance (Eq. 3).
    """
    return _cheb_impl(fvals, dct_t, scale, dispatch.kernel_mode(backend, use_kernel))


# -- candidate re-ranking ----------------------------------------------------


@functools.partial(jax.jit, static_argnames=("p", "mode", "blocks"))
def _rerank_impl(q, emb, ids, p, mode, blocks):
    if mode == "reference":
        return ref.rerank_ref(q, emb, ids, p)
    bb, bc = blocks
    return rerank_distances(q, emb, ids, p=p, bb=bb, bc=bc,
                            interpret=_interp(mode))


def candidate_distances(q, emb, ids, p: float = 2.0, use_kernel: bool = True,
                        backend: str | None = None):
    """Masked L^p re-rank distances over a database of embeddings.

    Args:
        q: (B, N) f32 queries.
        emb: (n_items, N) f32 stored embeddings.
        ids: (B, C) int32 candidate ids into ``emb``; -1 = empty slot.
        p: the L^p metric exponent (static).

    Returns:
        (B, C) f32 distances, +inf where ``ids`` is -1.  Prefer
        :func:`fused_query_topk` on the serving path -- it skips the
        (B, C, N) gather this op requires.
    """
    mode = dispatch.kernel_mode(backend, use_kernel)
    blocks = dispatch.rerank_blocks(q.shape[0], ids.shape[1])
    return _rerank_impl(q, emb, ids, p, mode, blocks)


# -- fused gather + rerank + top-k (the query-engine hot path) --------------


def _check_topk_width(op: str, k: int, mode: str) -> None:
    """The kernels keep top-k in ``_FUSED_TOPK_WIDTH`` lanes; a wider k is
    refused rather than quietly served by the reference path."""
    if mode != "reference" and k > _FUSED_TOPK_WIDTH:
        raise ValueError(
            f"{op}: k={k} exceeds the kernel's {_FUSED_TOPK_WIDTH}-lane "
            f"top-k scratch (mode {mode!r}); pass backend='reference' for "
            "the memory-bound jnp path")


@functools.partial(jax.jit,
                   static_argnames=("k", "p", "valid_items", "mode", "dedup"))
def _fused_query_impl(q, db, ids, live, k, p, valid_items, mode, dedup):
    if mode == "reference":
        if dedup:
            ids = ref.dedup_candidates(ids, db.shape[0], live)
        return ref.fused_query_topk_ref(q, db, ids, k, p, valid_items)
    return _fused_query_kernel_call(q, db, ids, k, p=p, valid_items=valid_items,
                                    interpret=_interp(mode), live=live,
                                    dedup=dedup)


def dedup_site(m: int, n: int, backend: str | None = None) -> str:
    """Where ``fused_query_topk`` / ``quantized_query_topk`` with
    ``dedup=True`` drop repeated and dead candidates of an (m, n) database:
    ``"kernel"``, inside the block kernel's candidate walk, or ``"table"``,
    in a first-seen table ahead of the tile kernel or the reference path.
    The same test of mode and shape the wrappers make."""
    mode = dispatch.query_backend(backend)
    return ("kernel" if mode != "reference" and _fused_resident(m, n)
            else "table")


def fused_query_topk(q, db, ids, k: int, p: float = 2.0,
                     valid_items: int | None = None,
                     backend: str | None = None, *, live=None,
                     dedup: bool = False):
    """Fused gather + L^p re-rank + top-k (the query hot path).

    Args:
        q: (nq, N) f32 queries.
        db: (n_items, N) f32 stored embeddings (taken into VMEM whole
            and each candidate's row gathered there, or past
            ``fused_query._RESIDENT_BYTES`` one row tile DMA'd per
            candidate -- the (nq, C, N) candidate tensor never exists in
            HBM).
        ids: (nq, C) int32 candidate ids into ``db``; -1 = empty slot.
        k: results per query (static).
        p: L^p exponent (static).
        valid_items: optionally mask ids >= this as invalid.
        backend: fused/reference/compiled/interpret
            (see ``dispatch.query_backend``).
        dedup: score each id once per query, at its first position, and
            with ``live`` ((n_items,) bool) no dead row; where it happens
            is :func:`dedup_site`.

    Returns:
        (dists (nq, k) f32 ascending, ids (nq, k) int32), -1/inf padded
        where fewer than k valid candidates exist; among equal distances
        the lower candidate position first (``lax.top_k``'s order) when
        the rows fit in VMEM.

    The kernel selects at most ``fused_query._KP`` results; a larger
    k raises in the kernel modes -- ask for ``backend="reference"`` (the
    memory-bound HBM-gather path) explicitly.
    """
    mode = dispatch.query_backend(backend)
    _check_topk_width("fused_query_topk", k, mode)
    return _fused_query_impl(q, db, ids, live, k, p, valid_items, mode,
                             dedup)


# -- quantized candidate scoring (the precision tier's query tail) -----------


@functools.partial(jax.jit,
                   static_argnames=("k", "p", "valid_items", "mode", "dedup"))
def _quantized_query_impl(q, codes, scale, ids, live, k, p, valid_items, mode,
                          dedup):
    if mode == "reference":
        if dedup:
            ids = ref.dedup_candidates(ids, codes.shape[0], live)
        return quantize.quantized_topk_ref(q, codes, scale, ids, k, p,
                                           valid_items)
    return quantize.quantized_query_topk(q, codes, scale, ids, k, p=p,
                                         valid_items=valid_items,
                                         interpret=_interp(mode), live=live,
                                         dedup=dedup)


def quantized_query_topk(q, codes, scale, ids, k: int, p: float = 2.0,
                         valid_items: int | None = None,
                         backend: str | None = None, *, live=None,
                         dedup: bool = False):
    """:func:`fused_query_topk` over a quantized (int8/bf16) database.

    Args as :func:`fused_query_topk`, plus ``codes`` (n_items, N) int8 or
    bf16 stored rows and ``scale`` the segment's symmetric dequant scale
    (scalar f32; 1.0 for bf16).  Scoring runs in code space (the query is
    mapped by ``round(q/scale)`` once) and distances are scaled back to the
    fp32 metric, so results from quantized and fp32 segments merge into one
    comparable pool.  Serve callers rescore the merged survivors exactly
    via ``quantize.rerank_survivors`` -- see docs/architecture.md
    § "The precision tier".
    """
    mode = dispatch.query_backend(backend)
    _check_topk_width("quantized_query_topk", k, mode)
    return _quantized_query_impl(q, codes, scale, ids, live, k, p,
                                 valid_items, mode, dedup)


# -- cross-segment top-k merge (the streaming serve layer's fan-in) ----------


def _sort_pairs(d, ids, mode: str):
    """Lexicographic (distance, id) sort -- the one primitive both merge
    wrappers share.  All three modes produce bit-identical output on
    NaN-free input (the order is total and there is no payload), so the
    merge *semantics* are mode-independent; only the lowering differs."""
    if mode == "sort":
        return jax.lax.sort((d, ids), num_keys=2, is_stable=True)
    if mode == "pallas":
        return merge_kernel.sort_pairs_pallas(
            d, ids, interpret=_interp(dispatch.kernel_mode()))
    return merge_kernel.sort_pairs(d, ids)


@functools.partial(jax.jit, static_argnames=("k", "mode"))
def _merge_topk_impl(dists, ids, k, mode):
    d = jnp.where(ids < 0, jnp.inf, dists)
    # lexicographic (distance, id) sort: deterministic under distance ties,
    # so a segmented query is bit-reproducible run to run.
    sd, si = _sort_pairs(d, ids.astype(jnp.int32), mode)
    sd, si = sd[..., :k], si[..., :k]
    return sd, jnp.where(jnp.isinf(sd), -1, si)


def _pad_to_k(dists, ids, k: int):
    """Right-pad the merge pool to at least k columns with (inf, -1) rows --
    shared by both merge wrappers so their padding semantics can't drift."""
    m = ids.shape[-1]
    if m < k:
        pad = k - m
        dists = jnp.pad(dists, ((0, 0), (0, pad)), constant_values=jnp.inf)
        ids = jnp.pad(ids, ((0, 0), (0, pad)), constant_values=-1)
    return dists, ids


def merge_topk(dists, ids, k: int, mode: str | None = None):
    """Merge per-shard top-k lists into a global top-k.

    The fan-in of both the cross-segment query (serve/segments.py) and the
    collective sharded query (core/distributed.py, inside shard_map).

    Args:
        dists/ids: (nq, M) f32/int32 -- M is the concatenation of every
            shard's k results (-1 id = empty slot).
        mode: merge implementation (bitonic/pallas/sort); default per
            ``dispatch.merge_backend``.  Bit-identical across modes.
    Returns:
        (dists (nq, k), ids (nq, k)), ascending by distance, -1/inf padded.

    The (distance, id) sort order is *total and stable*, which is what makes
    two-level merges (per-device, then across devices) bit-identical to one
    flat merge -- the sharding invariant leans on this.  The default
    bitonic network keeps the fan-in a fixed log^2(M) ladder of dense
    compare-exchange passes instead of a general ``sort(n_dev * k)``.
    """
    dists, ids = _pad_to_k(dists, ids, k)
    return _merge_topk_impl(dists, ids, k, dispatch.merge_backend(mode))


@functools.partial(jax.jit, static_argnames=("k", "mode"))
def _merge_topk_unique_impl(dists, ids, k, mode):
    d = jnp.where(ids < 0, jnp.inf, dists)
    ids = ids.astype(jnp.int32)
    sd, si = _sort_pairs(d, ids, mode)
    # Replicas of one segment return bit-identical (dist, gid) rows, so
    # duplicates are adjacent after the lexicographic sort; keep the first.
    dup = jnp.concatenate([jnp.zeros_like(si[..., :1], dtype=bool),
                           (si[..., 1:] == si[..., :-1]) & (si[..., 1:] >= 0)],
                          axis=-1)
    sd = jnp.where(dup, jnp.inf, sd)
    si = jnp.where(dup, -1, si)
    # Re-sort to push the masked duplicates past the top-k cut.  With no
    # duplicates this re-sort is the identity, so the result is
    # bit-identical to plain merge_topk.
    sd, si = _sort_pairs(sd, si, mode)
    sd, si = sd[..., :k], si[..., :k]
    return sd, jnp.where(jnp.isinf(sd), -1, si)


def merge_topk_unique(dists, ids, k: int, mode: str | None = None):
    """:func:`merge_topk` that additionally dedups by id.

    The fan-in of the **replicated** sharded query
    (core/distributed.py): when a hot segment is materialized on several
    devices, the same (dist, gid) row can reach the collective merge once
    per answering replica; keeping only the first occurrence makes the
    merged top-k identical to the unreplicated path.  On duplicate-free
    input this is bit-identical to :func:`merge_topk` (the dedup mask is
    empty and the second sort is the identity), which is why the
    replicated serve path can use it unconditionally.
    """
    dists, ids = _pad_to_k(dists, ids, k)
    return _merge_topk_unique_impl(dists, ids, k, dispatch.merge_backend(mode))
