"""Lazy kernel-backend selection + per-shape block-size heuristics.

Replaces the import-time ``_ON_TPU`` constant: the platform is probed on
first use (so ``JAX_PLATFORMS`` set after import still wins) and every
decision can be overridden per call or per process.

Two independent choices are made here:

* **kernel mode** -- how a Pallas kernel executes when it runs at all:
  ``"compiled"`` (real Mosaic lowering, TPU) or ``"interpret"``
  (``interpret=True``, the CPU validation path).  ``"reference"`` short-
  circuits to the pure-jnp oracle in :mod:`repro.kernels.ref`.
  Default: compiled on TPU, interpret elsewhere.  Override with the
  ``REPRO_KERNEL_BACKEND`` env var or an explicit ``backend=`` argument.

* **query backend** -- which re-rank path ``core.index.query_index`` takes:
  ``"fused"`` (the gather+rerank+top-k kernel in fused_query.py) or
  ``"reference"`` (gather to HBM + jnp re-rank + ``lax.top_k``).
  Default: fused on TPU, reference on CPU -- interpret-mode execution of
  the kernel's per-candidate gather loop is correct but far too slow to be
  a production CPU path (it exists for parity tests and benchmarks).
  Override with ``REPRO_QUERY_BACKEND`` or ``backend=``.

Block sizes: MXU/VPU-aligned 128 tiles when a dimension is large enough,
else the dimension rounded up to the 8-sublane quantum so small problems
don't pay 16x padding waste.

The env-knob table (values, defaults, which op each governs) is maintained
in EXPERIMENTS.md § "Kernel dispatch".
"""

from __future__ import annotations

import functools
import os

import jax

KERNEL_MODES = ("compiled", "interpret", "reference")
QUERY_BACKENDS = ("fused", "reference")
STORE_DTYPES = ("fp32", "bf16", "int8")
MERGE_BACKENDS = ("bitonic", "pallas", "sort")

_ENV_KERNEL = "REPRO_KERNEL_BACKEND"
_ENV_QUERY = "REPRO_QUERY_BACKEND"
_ENV_STORE = "REPRO_STORE_DTYPE"
_ENV_MERGE = "REPRO_MERGE_BACKEND"


@functools.lru_cache(maxsize=None)
def _platform() -> str:
    """Probed lazily so tests/env-vars set after import are respected."""
    return jax.default_backend()


def clear_cache() -> None:
    """Forget the probed platform (tests that flip JAX_PLATFORMS)."""
    _platform.cache_clear()


def kernel_mode(override: str | None = None, use_kernel: bool = True) -> str:
    """Resolve how a Pallas op should execute.

    Resolution order: ``use_kernel=False`` (legacy escape hatch) >
    explicit ``override`` > ``$REPRO_KERNEL_BACKEND`` > platform default.
    Must be called *outside* jit-traced code paths only in the sense that
    it reads process state; the returned mode is then baked in as a static
    argument.
    """
    if not use_kernel:
        return "reference"
    if override is not None:
        mode = override
    else:
        mode = os.environ.get(_ENV_KERNEL) or (
            "compiled" if _platform() == "tpu" else "interpret")
    if mode not in KERNEL_MODES:
        raise ValueError(f"unknown kernel mode {mode!r}; want one of {KERNEL_MODES}")
    return mode


def query_backend(override: str | None = None) -> str:
    """Resolve the index query path: 'fused' or 'reference'.

    Accepts kernel modes too ('interpret'/'compiled' imply the fused path
    run in that mode; 'reference' is the jnp path), so callers can say
    ``query_index(..., backend="interpret")`` to force interpret-mode
    validation of the fused kernel on CPU.
    """
    mode = override or os.environ.get(_ENV_QUERY) or (
        "fused" if _platform() == "tpu" else "reference")
    if mode in ("compiled", "interpret"):
        return mode
    if mode == "fused":
        return "compiled" if _platform() == "tpu" else "interpret"
    if mode == "reference":
        return "reference"
    raise ValueError(
        f"unknown query backend {mode!r}; want fused/reference/compiled/interpret")


def hash_backend() -> str:
    """Kernel mode for index hashing (build *and* query).

    Bucket assignment must be bit-identical between ``build_index`` and
    ``query_index`` -- a floor() that flips at a bin boundary moves an item
    to a different bucket than the one probed at query time.  So the index
    always hashes through ONE process-constant implementation; per-call
    overrides are deliberately not offered here.  Defaults to the pure-jnp
    reference on CPU (fast) and the compiled kernel on TPU; an explicit
    ``$REPRO_KERNEL_BACKEND`` still wins so TPU-less CI can exercise the
    kernel path end to end.
    """
    env = os.environ.get(_ENV_KERNEL)
    if env:
        return kernel_mode(env)
    return "compiled" if _platform() == "tpu" else "reference"


def embed_backend(override: str | None = None) -> str:
    """Kernel mode for the embedder layer (``repro.embedders``).

    Like :func:`query_backend` for the re-rank tail: the production default
    is the compiled kernel on TPU and the pure-jnp reference on CPU --
    interpret-mode embedding exists for kernel validation, not serving (the
    interpreter re-materialises operands per grid step).  The reference
    path is also what keeps the embedder refactor bit-identical to the old
    inline serve-registry code on CPU.  An explicit ``override`` or
    ``$REPRO_KERNEL_BACKEND`` still wins, so TPU-less CI can exercise the
    kernel path end to end.
    """
    mode = override or os.environ.get(_ENV_KERNEL)
    if mode:
        return kernel_mode(mode)
    return "compiled" if _platform() == "tpu" else "reference"


def store_dtype(override: str | None = None) -> str:
    """Resolve the sealed-segment storage precision tier.

    Resolution order: ``$REPRO_STORE_DTYPE`` > explicit ``override`` (the
    tenant spec's ``precision`` field) > ``"fp32"``.  The env var wins over
    the spec on purpose -- it is the operator's fleet-wide capacity lever,
    and the registry resolves it ONCE at tenant registration so the
    precision that actually served is the one recorded in the WAL REGISTER
    record and every snapshot (recovery never re-reads the env).
    ``fp32`` is bit-exact (no quantized representation is ever built);
    ``bf16``/``int8`` are the bounded-loss tiers (invariant 10).
    """
    mode = os.environ.get(_ENV_STORE) or override or "fp32"
    if mode not in STORE_DTYPES:
        raise ValueError(
            f"unknown store dtype {mode!r}; want one of {STORE_DTYPES}")
    return mode


def merge_backend(override: str | None = None) -> str:
    """Resolve the top-k merge fan-in implementation.

    ``"bitonic"`` (default) runs the fixed-topology compare-exchange
    network in kernels/merge.py as plain jnp; ``"pallas"`` runs the same
    network inside one Pallas kernel (compiled on TPU, interpret
    elsewhere); ``"sort"`` is the legacy ``jax.lax.sort`` path.  All three
    are bit-identical on NaN-free input (tests/test_merge_bitonic.py), so
    this knob moves cost, never results.  Resolution: explicit ``override``
    > ``$REPRO_MERGE_BACKEND`` > ``"bitonic"``.  Serve-layer collectives
    cache traces keyed on (cfg, k, ...), so like ``hash_backend`` the env
    choice is effectively process-constant -- set it before first query.
    """
    mode = override or os.environ.get(_ENV_MERGE) or "bitonic"
    if mode not in MERGE_BACKENDS:
        raise ValueError(
            f"unknown merge backend {mode!r}; want one of {MERGE_BACKENDS}")
    return mode


def describe() -> dict:
    """Every dispatch decision as it would resolve *right now*, plus the
    env overrides that produced it -- the observability hook the serve
    report and the telemetry exporter publish so an operator can tell
    which code path a deployment is actually running without reading env
    vars off the process.
    """
    return {
        "platform": _platform(),
        "kernel_mode": kernel_mode(),
        "query_backend": query_backend(),
        "hash_backend": hash_backend(),
        "embed_backend": embed_backend(),
        "store_dtype": store_dtype(),
        "merge_backend": merge_backend(),
        "env": {_ENV_KERNEL: os.environ.get(_ENV_KERNEL),
                _ENV_QUERY: os.environ.get(_ENV_QUERY),
                _ENV_STORE: os.environ.get(_ENV_STORE),
                _ENV_MERGE: os.environ.get(_ENV_MERGE)},
    }


# ---------------------------------------------------------------------------
# Per-shape block-size selection
# ---------------------------------------------------------------------------


def _fit(dim: int, target: int = 128, quantum: int = 8) -> int:
    """target if the dim fills it, else the dim rounded up to the quantum."""
    if dim >= target:
        return target
    return max(quantum, -(-dim // quantum) * quantum)


def matmul_blocks(b: int, n: int, k: int) -> tuple[int, int, int]:
    """(bm, bn, bk) for a (B,N)@(N,K) kernel: 128-cubed when saturated,
    shrunk (8-quantum) on small dims to avoid padding waste."""
    return _fit(b), _fit(n), _fit(k)


def rerank_blocks(b: int, c: int) -> tuple[int, int]:
    """(bb, bc) for the (B, C, N) re-rank kernel."""
    return _fit(b, target=8), _fit(c)
