"""1-D distributions of raw Gaussian draws, embedded for W^p search.

Each item is ``draws`` samples of N(mu, sigma^2), mu ~ U(mu), sigma ~
U(sigma) (the launcher's synthetic Wasserstein tenant).  Its embedding is
the empirical quantile function read at ``n_dims`` levels spread evenly
over ``[clip, 1 - clip]``, times ``((1 - 2 clip) / n_dims)^(1/p)``, so the
l^p distance of two embeddings is the Monte Carlo estimate of their
(clipped) W^p distance (arXiv:2002.03909, Sec. 2.2, Remark 1).  The
midpoint levels equal the first ``n_dims`` points of the base-2
low-discrepancy sequence after its first ``n_dims`` are skipped.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def generate(key, n: int, n_dims: int, params: dict):
    """(n, n_dims) float32 embeddings; jittable, deterministic in ``key``."""
    draws = int(params["draws"])
    clip = float(params["clip"])
    p = float(params["p"])
    k_mu, k_sig, k_x = jax.random.split(key, 3)
    mu = jax.random.uniform(k_mu, (n, 1), jnp.float32, *params["mu"])
    sig = jax.random.uniform(k_sig, (n, 1), jnp.float32, *params["sigma"])
    x = mu + sig * jax.random.normal(k_x, (n, draws), jnp.float32)
    levels = clip + (1.0 - 2.0 * clip) * (np.arange(n_dims) + 0.5) / n_dims
    idx = np.clip(np.floor(levels * draws).astype(np.int32), 0, draws - 1)
    quantiles = jnp.sort(x, axis=-1)[:, idx]
    scale = ((1.0 - 2.0 * clip) / n_dims) ** (1.0 / p)
    return (quantiles * jnp.float32(scale)).astype(jnp.float32)
