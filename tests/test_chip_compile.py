"""Compile-only checks of the served-path Pallas kernels for a TPU v5e.

Each test lowers one kernel with ``interpret=False`` for a v5e chip that is
described (``jax.experimental.topologies``), not attached, at the shapes
the launcher's three-tenant deployment dispatches (``launch.serve``
``default_specs``: N=64, 8 tables x 4 hashes, bucket capacity 32, the
(8, 32, 128) chunk palette, ``--n-probes 4`` -> C = 8*4*32 = 1024
candidates, k=10), and asserts the Mosaic kernel is in the compiled
program.  Nothing runs, so this says nothing about results or speed; it
catches what the TPU compiler refuses (block shapes off the native tile,
SMEM or VMEM overflow, unsupported primitives) at no chip time.

The topology is described only inside the module-scoped fixture: only one
process may load the TPU library at a time, and describing it while
pytest-xdist workers import this file would make them collect different
tests.  The persistent compile cache is off around these compiles: a
described chip's executables cannot be read back from it.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dispatch, quantize
from repro.kernels.dct_mm import dct_mm
from repro.kernels.fused_query import fused_query_topk
from repro.kernels.hash_mm import hash_mm

N = 64                       # ServableSpec.n_dims / --n-dims
LK = 8 * 4                   # n_tables * n_hashes
C = 8 * 4 * 32               # n_tables * n_probes * bucket_capacity
SEG = 1024                   # --segment-capacity (rows per segment db)
K = 10                       # --k
PALETTE = (8, 32, 128)       # ServableSpec.chunk_sizes
INSERT_CHUNK = 256           # ServableSpec.insert_chunk


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:             # noqa: BLE001 -- any failure = no TPU
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("return_proj", [False, True])
@pytest.mark.parametrize("rows", PALETTE + (INSERT_CHUNK,))
def test_hash_mm_compiles(one_chip, rows, return_proj):
    bm, bn, bk = dispatch.matmul_blocks(rows, N, LK)
    fn = functools.partial(hash_mm, r=4.0, bm=bm, bn=bn, bk=bk,
                           interpret=False, return_proj=return_proj)
    txt = _compiled_text(fn, _spec(one_chip, (rows, N)),
                         _spec(one_chip, (N, LK)), _spec(one_chip, (LK,)))
    assert "tpu_custom_call" in txt


def test_dct_mm_compiles(one_chip):
    # the basis tenant embeds through embed_batched's 128-row chunks
    fn = functools.partial(dct_mm, interpret=False)
    txt = _compiled_text(fn, _spec(one_chip, (max(PALETTE), N)),
                         _spec(one_chip, (N, N)), _spec(one_chip, (N,)))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("p", [1.0, 2.0])
@pytest.mark.parametrize("nq", PALETTE)
def test_fused_query_topk_compiles(one_chip, nq, p):
    fn = functools.partial(fused_query_topk, k=K, p=p, interpret=False)
    txt = _compiled_text(fn, _spec(one_chip, (nq, N)),
                         _spec(one_chip, (SEG, N)),
                         _spec(one_chip, (nq, C), jnp.int32))
    assert "tpu_custom_call" in txt


def test_fused_query_topk_compiles_past_one_smem_table(one_chip):
    # nq * C * 4 bytes > the per-call id-table budget: the wrapper splits
    # the queries into several kernel calls instead of overflowing SMEM
    fn = functools.partial(fused_query_topk, k=K, p=2.0, interpret=False)
    txt = _compiled_text(fn, _spec(one_chip, (128, N)),
                         _spec(one_chip, (SEG, N)),
                         _spec(one_chip, (128, 4 * C), jnp.int32))
    assert txt.count("tpu_custom_call") >= 4


@pytest.mark.parametrize("k", [quantize.survivor_width(K, 0, C), 128])
@pytest.mark.parametrize("nq", PALETTE)
def test_quantized_query_topk_int8_compiles(one_chip, nq, k):
    fn = functools.partial(quantize.quantized_query_topk, k=k, p=2.0,
                           interpret=False)
    txt = _compiled_text(fn, _spec(one_chip, (nq, N)),
                         _spec(one_chip, (SEG, N), jnp.int8),
                         _spec(one_chip, (), jnp.float32),
                         _spec(one_chip, (nq, C), jnp.int32))
    assert "tpu_custom_call" in txt
