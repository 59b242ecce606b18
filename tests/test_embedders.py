"""Embedder layer: refactor parity, registry resolution, Wasserstein geometry.

The load-bearing tests are the **bit-parity** ones: the basis/QMC embedders
replaced inline branches in ``serve.registry``, and the refactor contract
is that the new layer produces *bit-identical* embeddings and node sets
for p in {1, 2} -- an embedding that drifts by 1 ulp can flip an item
across a hash-bucket boundary and silently change every downstream result.

Bitwise equality holds for one batch shape.  Across batch shapes (chunked
vs one-shot) the basis embedding goes through an XLA dot whose blocking --
and so the summation order of each row's N-term sum -- XLA picks per
shape.  Those comparisons use :func:`_assert_same_up_to_dot_order`.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import basis, montecarlo, wasserstein
from repro.embedders import (BasisEmbedder, QMCEmbedder, WassersteinEmbedder,
                             embedder_names, make_embedder)
from repro.serve import ServableRegistry, ServableSpec

N = 32


def _fvals(b=23, n=N, seed=0):
    return np.random.default_rng(seed).normal(size=(b, n)).astype(np.float32)


def _assert_same_up_to_dot_order(got, want):
    """Equal up to the summation order of an N-term f32 dot per row: a
    reordered sum of N terms differs by at most ~N ulps of its largest
    term, so each row may move by N ulps of its largest coefficient."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    row_max = np.abs(want).max(axis=1, keepdims=True).astype(np.float32)
    tol = want.shape[1] * np.spacing(row_max)
    assert (np.abs(got - want) <= tol).all(), np.abs(got - want).max()


# ---------------------------------------------------------------------------
# refactor parity: bit-identical to the pre-embedders inline paths
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("p", [1.0, 2.0])
def test_basis_embedder_bitwise_parity(p):
    """BasisEmbedder.embed == the old inline ``cheb_l2_coeffs(fvals)``."""
    fv = _fvals()
    old = np.asarray(basis.cheb_l2_coeffs(jnp.asarray(fv)))
    e = make_embedder("basis", N, p=p)
    np.testing.assert_array_equal(np.asarray(e.embed(fv)), old)
    np.testing.assert_array_equal(
        e.nodes(), np.asarray(basis.cheb_nodes(N)))


@pytest.mark.parametrize("p", [1.0, 2.0])
def test_qmc_embedder_bitwise_parity(p):
    """QMCEmbedder.embed == the old inline ``mc_embedding(fvals, V, p)``."""
    fv = _fvals(seed=1)
    for volume in (1.0, 2.5):
        old = np.asarray(montecarlo.mc_embedding(jnp.asarray(fv), volume,
                                                 p=p))
        e = make_embedder("qmc", N, p=p, volume=volume)
        np.testing.assert_array_equal(np.asarray(e.embed(fv)), old)
    np.testing.assert_array_equal(
        e.nodes(), np.asarray(montecarlo.qmc_nodes(N))[:, 0])


@pytest.mark.parametrize("embedder", ["basis", "qmc"])
def test_servable_embed_bitwise_parity(embedder):
    """The serve-layer refactor end to end: Servable.embed through the new
    registry-resolved, palette-batched path == the old inline branch."""
    fv = _fvals(b=200, seed=2)          # > max chunk: exercises the padding
    reg = ServableRegistry()
    sv = reg.register(ServableSpec(
        name="t", n_dims=N, p=2.0 if embedder == "basis" else 1.0,
        embedder=embedder, volume=1.0, segment_capacity=128,
        insert_chunk=64, chunk_sizes=(8, 32)))
    got = np.asarray(sv.embed(fv))
    if embedder == "basis":
        want = np.asarray(basis.cheb_l2_coeffs(jnp.asarray(fv)))
        want_nodes = np.asarray(basis.cheb_nodes(N))
    else:
        want = np.asarray(montecarlo.mc_embedding(jnp.asarray(fv), 1.0,
                                                  p=1.0))
        want_nodes = np.asarray(montecarlo.qmc_nodes(N))[:, 0]
    if embedder == "basis":
        _assert_same_up_to_dot_order(got, want)    # 32-row chunks vs one shot
    else:
        np.testing.assert_array_equal(got, want)    # elementwise: exact
    np.testing.assert_array_equal(sv.nodes(), want_nodes)


def test_embed_batched_padding_is_invisible():
    """Chunked+padded embedding == one-shot, ragged tail included: pad rows
    never leak into real rows (bitwise for one chunk shape, up to dot
    order across chunk shapes)."""
    e = make_embedder("basis", N)
    fv = _fvals(b=77, seed=3)           # 77 = 2*32 + 13 ragged tail
    one = np.asarray(e.embed(fv))
    _assert_same_up_to_dot_order(e.embed_batched(fv, batch_size=32), one)
    _assert_same_up_to_dot_order(e.embed_batched(fv, batch_size=128), one)
    # same chunk shape, different padding: the real rows are bitwise equal
    np.testing.assert_array_equal(
        np.asarray(e.embed_batched(fv[:70], batch_size=32)),
        np.asarray(e.embed_batched(fv, batch_size=32))[:70])


def test_basis_kernel_path_matches_reference():
    """The fused DCT kernel route (interpret mode on CPU) stays numerically
    on top of the eager reference path."""
    e = make_embedder("basis", N)
    fv = _fvals(seed=4)
    ref = np.asarray(e.embed(fv, backend="reference"))
    ker = np.asarray(e.embed(fv, backend="interpret"))
    np.testing.assert_allclose(ker, ref, atol=1e-5)


def test_legendre_basis_parity():
    e = make_embedder("basis", 16, params={"basis": "legendre"})
    assert e.nodes().shape == (32,)     # 2N quadrature samples
    fv = _fvals(b=5, n=32, seed=5)
    want = np.asarray(basis.legendre_l2_coeffs(jnp.asarray(fv), n_coeff=16))
    np.testing.assert_array_equal(np.asarray(e.embed(fv)), want)


# ---------------------------------------------------------------------------
# registry + params round-trip
# ---------------------------------------------------------------------------


def test_registry_names_and_unknown():
    assert set(embedder_names()) >= {"basis", "qmc", "wasserstein"}
    with pytest.raises(ValueError, match="unknown embedder"):
        make_embedder("nope", N)
    with pytest.raises(ValueError):
        ServableSpec(name="bad", embedder="nope")


@pytest.mark.parametrize("name,params", [
    ("basis", {"interval": [0.0, 2.0], "measure": "theta"}),
    ("qmc", {"sequence": "halton", "skip": 32}),
    ("qmc", {"sequence": "mc", "seed": 7}),
    ("wasserstein", {"clip": 0.01, "sequence": "halton"}),
])
def test_params_round_trip(name, params):
    """make_embedder(name, ..., params=e.params()) rebuilds an embedder with
    identical nodes and embeddings (the checkpoint-manifest contract)."""
    e1 = make_embedder(name, N, p=2.0, volume=1.5, params=params)
    e2 = make_embedder(name, N, p=2.0, volume=1.5, params=e1.params())
    np.testing.assert_array_equal(e1.nodes(), e2.nodes())
    x = _fvals(b=6, n=N if name != "wasserstein" else 100, seed=6)
    np.testing.assert_array_equal(np.asarray(e1.embed(x)),
                                  np.asarray(e2.embed(x)))
    import json
    json.dumps(e1.describe())           # reports/manifests need JSON-able


def test_late_registration_is_deployable():
    """An embedder registered after the serve layer imports must be
    accepted by ServableSpec -- the @register_embedder extension point."""
    from repro.embedders import register_embedder
    from repro.embedders.base import _FACTORIES

    @register_embedder("test-identity")
    class _IdentityEmbedder(QMCEmbedder):
        pass

    try:
        spec = ServableSpec(name="t", n_dims=N, embedder="test-identity",
                            segment_capacity=128, chunk_sizes=(8,))
        sv = ServableRegistry().register(spec)
        assert np.asarray(sv.embed(_fvals(b=3))).shape == (3, N)
    finally:
        _FACTORIES.pop("test-identity", None)


def test_embedder_types():
    assert isinstance(make_embedder("basis", N), BasisEmbedder)
    assert isinstance(make_embedder("qmc", N), QMCEmbedder)
    assert isinstance(make_embedder("wasserstein", N), WassersteinEmbedder)


# ---------------------------------------------------------------------------
# Wasserstein embedder geometry
# ---------------------------------------------------------------------------


def test_wasserstein_embedding_distance_matches_w2():
    """||T(F^-1) - T(G^-1)||_2 approximates the closed-form W2."""
    e = make_embedder("wasserstein", 512)
    mu = np.asarray([0.0, 0.4, -0.8], np.float32)
    sig = np.asarray([1.0, 0.6, 0.3], np.float32)
    emb = np.asarray(e.embed_gaussian(mu, sig))
    for i in range(3):
        for j in range(i + 1, 3):
            est = float(np.linalg.norm(emb[i] - emb[j]))
            true = float(wasserstein.gaussian_w2(mu[i], sig[i],
                                                 mu[j], sig[j]))
            assert abs(est - true) < 0.03 + 0.05 * true


def test_wasserstein_empirical_matches_parametric():
    """Raw draws land next to the closed-form quantile embedding of the same
    distribution -- one index serves both input forms."""
    e = make_embedder("wasserstein", 64)
    rng = np.random.default_rng(8)
    mu, sig = 0.3, 0.7
    samples = (mu + sig * rng.normal(size=(1, 8000))).astype(np.float32)
    emp = np.asarray(e.embed(samples))[0]
    par = np.asarray(e.embed_gaussian(np.float32(mu), np.float32(sig)))
    assert np.linalg.norm(emp - par) < 0.05
    # quantile levels live strictly inside the clipped interval
    u = e.nodes()
    assert u.min() >= e.clip and u.max() <= 1.0 - e.clip
    assert e.volume == pytest.approx(1.0 - 2 * e.clip)


def test_wasserstein_clip_validation():
    with pytest.raises(ValueError, match="clip"):
        make_embedder("wasserstein", N, params={"clip": 0.5})
