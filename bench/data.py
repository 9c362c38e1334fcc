"""Corpus and query generation from ``--seed``, on the device.

A copy of the generator the program ships
(``repro.data.synthetic.embedding_dataset``): anisotropic covariance with
a power-law spectrum, a mean shift and Gaussian cluster structure,
optionally normalized to the unit sphere.  The copy splits the draw in
two, so that the corpus and the query pool come from one distribution
but from keys of their own: the corpus then does not depend on how many
queries a run draws.

What the seed draws: the queries, the arrival order (``loadgen``), and a
sign for every coordinate, applied to corpus and queries alike.  The
corpus before the signs, and the key of the index build, come from the
configuration's ``dataset_seed``.  A sign flip is exact in floating
point and commutes with rounding, so every distance and every k-means
assignment is the same bit for bit: each seed gives the index the same
inverted lists, hence the same shapes and the same work, in coordinates
of its own.  The benchmark regenerates the corpus for its reference, so
nothing here may read what the program made.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A JAX key from any non-negative integer seed (wider than 32 bits
    too): the seed goes through numpy's ``SeedSequence`` into the two
    32-bit words of a threefry key."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def keys(seed: int) -> dict:
    """The run's independent streams: coordinate signs, queries."""
    k = jax.random.split(seed_key(seed), 2)
    return dict(signs=k[0], queries=k[1])


def dataset_keys(cfg: dict) -> dict:
    """The configuration's fixed streams: distribution, corpus, build."""
    k = jax.random.split(seed_key(cfg["generator"]["dataset_seed"]), 3)
    return dict(dist=k[0], corpus=k[1], build=k[2])


def _params(key, dim, n_clusters, spectrum_pow, cluster_spread):
    k1, k2 = jax.random.split(key)
    spectrum = jnp.arange(1, dim + 1, dtype=jnp.float32) ** -spectrum_pow
    A = jax.random.normal(k1, (dim, dim)) * spectrum[None, :]
    centers = jnp.matmul(
        jax.random.normal(k2, (n_clusters, dim)), A.T,
        precision="highest",
    ) * cluster_spread
    return A, centers


@functools.partial(
    jax.jit,
    static_argnames=("n", "dim", "n_clusters", "spectrum_pow",
                     "cluster_spread", "mean_shift", "normalize"),
)
def _rows(dist_key, row_key, sign_key, *, n, dim, n_clusters,
          spectrum_pow, cluster_spread, mean_shift, normalize):
    A, centers = _params(
        dist_key, dim, n_clusters, spectrum_pow, cluster_spread
    )
    k3, k4 = jax.random.split(row_key)
    assign = jax.random.randint(k3, (n,), 0, n_clusters)
    X = jnp.matmul(
        jax.random.normal(k4, (n, dim)), A.T, precision="highest"
    ) + centers[assign] + mean_shift
    if normalize:
        X = X / jnp.linalg.norm(X, axis=-1, keepdims=True)
    signs = jax.random.rademacher(sign_key, (dim,), dtype=jnp.float32)
    return X * signs[None, :]


def _draw(cfg: dict, row_key, seed: int, n: int):
    gen = cfg["generator"]
    return _rows(
        dataset_keys(cfg)["dist"], row_key, keys(seed)["signs"],
        n=int(n), dim=int(cfg["dim"]),
        n_clusters=int(gen["n_clusters"]),
        spectrum_pow=float(gen["spectrum_pow"]),
        cluster_spread=float(gen["cluster_spread"]),
        mean_shift=float(gen["mean_shift"]),
        normalize=bool(gen["normalize"]),
    )


def corpus(cfg: dict, seed: int):
    """(n_rows, dim) float32 corpus on the default device."""
    return _draw(cfg, dataset_keys(cfg)["corpus"], seed, cfg["n_rows"])


def queries(cfg: dict, seed: int, n: int) -> np.ndarray:
    """(n, dim) float32 query rows on the host, from the corpus's
    distribution; every row is distinct."""
    return np.asarray(_draw(cfg, keys(seed)["queries"], seed, n))
