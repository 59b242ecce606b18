"""Smooth random functions on [0, 1], embedded for L^p search.

Each item is a sum of ``terms`` sines, amplitude N(0, 1) / terms, frequency
U(freq) and phase U(0, 2 pi) (the launcher's synthetic function tenants),
sampled at ``n_dims`` evenly spread nodes and scaled by
``(volume / n_dims)^(1/p)``: the Monte Carlo node embedding of Eq. 6 of
arXiv:2002.03909.  The midpoint nodes equal the first ``n_dims`` points
of the base-2 low-discrepancy sequence after its first ``n_dims`` are
skipped.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def generate(key, n: int, n_dims: int, params: dict):
    """(n, n_dims) float32 embeddings; jittable, deterministic in ``key``."""
    terms = int(params["terms"])
    p = float(params["p"])
    k_a, k_f, k_p = jax.random.split(key, 3)
    amps = jax.random.normal(k_a, (n, terms, 1), jnp.float32) / terms
    freqs = jax.random.uniform(k_f, (n, terms, 1), jnp.float32,
                               *params["freq"])
    phase = jax.random.uniform(k_p, (n, terms, 1), jnp.float32,
                               0.0, 2.0 * np.pi)
    nodes = jnp.asarray((np.arange(n_dims) + 0.5) / n_dims, jnp.float32)
    vals = jnp.sum(amps * jnp.sin(freqs * nodes + phase), axis=1)
    scale = (float(params["volume"]) / n_dims) ** (1.0 / p)
    return (vals * jnp.float32(scale)).astype(jnp.float32)
