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


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8])
def test_fused_query_topk_compiles_past_vmem(one_chip, dtype):
    # a whole index's rows (query_index, query_distributed) are past the
    # block kernel's VMEM budget: the tile kernel answers them
    from repro.kernels import fused_query

    m = 65536
    assert not fused_query._resident(m, N)
    fn = functools.partial(fused_query_topk, k=K, p=2.0, interpret=False)
    txt = _compiled_text(fn, _spec(one_chip, (PALETTE[0], N)),
                         _spec(one_chip, (m, N), dtype),
                         _spec(one_chip, (PALETTE[0], C), jnp.int32))
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


def _stacked_fan_out(sharding, precision, p, slots):
    """The batch's one fan-out program over ``slots`` stacked sealed
    segments, compiled for ``sharding``'s chip, and its top-k width."""
    from repro.core.index import IndexConfig, LSHIndexState
    from repro.serve import segments as segmod

    nq, lk, tables, buckets = PALETTE[0], LK, 8, 1024
    cfg = IndexConfig(n_dims=N, n_tables=tables, n_hashes=lk // tables,
                      log2_buckets=10, bucket_capacity=32, r=0.5, p=p)
    quantized = precision == "int8"
    width = quantize.survivor_width(K, 0, C) if quantized else K

    def state(*lead, db=jnp.float32):
        return LSHIndexState(
            alpha=_spec(sharding, lead + (N, lk)),
            b=_spec(sharding, lead + (lk,)),
            mix=_spec(sharding, lead + (tables, lk // tables), jnp.uint32),
            table=_spec(sharding, lead + (tables, buckets, 32), jnp.int32),
            counts=_spec(sharding, lead + (tables, buckets), jnp.int32),
            db=_spec(sharding, lead + (SEG, N), db))

    fn = segmod._stacked_query_fn(cfg, width, 4, "compiled", quantized)
    compiled = fn.lower(
        state(slots, db=jnp.int8 if quantized else jnp.float32),
        _spec(sharding, (slots, SEG), jnp.int32),
        _spec(sharding, (slots, SEG), jnp.bool_),
        _spec(sharding, (slots,)), _spec(sharding, (), jnp.int32),
        state(), _spec(sharding, (SEG,), jnp.int32),
        _spec(sharding, (SEG,), jnp.bool_),
        _spec(sharding, (nq, N))).compile()
    return compiled, width


@pytest.mark.parametrize("precision,p,slots", [("fp32", 2.0, 510),
                                               ("int8", 1.0, 255)])
def test_stacked_fan_out_compiles(one_chip, precision, p, slots):
    """The batch's one fan-out program over the stacked sealed segments
    (255 slots: 2^18 items loaded; 510: grown once by a seal): the query
    kernel runs once in the slot loop, on one segment's 2-D rows as per
    segment, and once for the delta, each with the segment's live row
    after the rows (it drops repeated and dead candidates itself, so no
    first-seen table is scattered); the stacked tables are read in place,
    neither copied nor sliced per slot."""
    import re

    nq, tables, buckets = PALETTE[0], 8, 1024
    quantized = precision == "int8"
    txt = _stacked_fan_out(one_chip, precision, p, slots)[0].as_text()
    calls = re.findall(
        r"%_(fused|quantized)_query_impl[.\d]* = .*operand_layout_"
        r"constraints=\{s32\[(\d+)\]\{0\}, f32\[(\d+),1,64\]\{2,1,0\}, "
        r"(f32|s8)\[(\d+),64\]\{1,0\}, s32\[(\d+)\]\{0\}\}", txt)
    assert txt.count("tpu_custom_call") == len(calls) == 2
    for _, ids, q_rows, _, n_rows, n_live in calls:
        assert (int(ids), int(q_rows), int(n_rows), int(n_live)) == (
            nq * C, nq, SEG, SEG)
    assert not re.search(rf"s32\[{nq * SEG}\]\{{[^}}]*\}} scatter\(", txt)
    assert {c[0] for c in calls} == (
        {"quantized", "fused"} if quantized else {"fused"})
    assert not re.search(rf"s32\[{slots},{tables},{buckets},32\]\{{[^}}]*\}} "
                         r"copy\(", txt)
    assert not re.search(rf"s32\[(1,)?{tables},{buckets},32\]\{{[^}}]*\}} "
                         r"dynamic-slice\(", txt)


@pytest.mark.parametrize("precision,p,slots", [("fp32", 2.0, 510),
                                               ("int8", 1.0, 255)])
def test_stacked_fan_out_kernel_calls_parse(one_chip, precision, p, slots):
    """The roofline readers find the stacked program's query-kernel calls:
    ``chipbench.work.fused_query.KERNEL`` parses each custom call's text
    as a device trace names it (operands with their shapes) into the
    shapes the work model counts."""
    from jax._src.lib import xla_client

    from chipbench.work import fused_query as work

    compiled, width = _stacked_fan_out(one_chip, precision, p, slots)
    opts = xla_client._xla.HloPrintOptions()
    opts.print_operand_shape = True
    opts.print_metadata = False
    opts.print_backend_config = False
    ops = [line.strip()
           for module in compiled.runtime_executable().hlo_modules()
           for line in module.to_string(opts).splitlines()
           if "_query_impl" in line and "custom-call(" in line]
    calls = work.calls([(op, 0, 1e6) for op in ops])
    assert len(ops) == len(calls) == 2
    stored = 1 if precision == "int8" else 4
    assert sorted(c[:5] for c in calls) == sorted(
        [(PALETTE[0], C, N, width, stored), (PALETTE[0], C, N, width, 4)])
