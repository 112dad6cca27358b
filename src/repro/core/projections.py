"""Direction-finding for ProHD: centroid axis + top principal components.

The paper (Alg. 1/2) computes (a) the unit vector between the two cloud
centroids and (b) the top ``m = floor(sqrt(D))`` principal components of the
stacked cloud ``[A; B]``.

TPU adaptation (DESIGN.md §3): instead of a LAPACK truncated SVD we offer three
interchangeable PCA backends:

- ``gram``:   accumulate the D×D Gram/covariance matrix (one big MXU matmul,
              one psum when distributed) and ``eigh`` it.  O(n D²) flops but
              matmul-bound; the right choice for D ≤ a few thousand.
- ``rsvd``:   randomized range-finder SVD (Halko et al.) — O(n D m) like the
              paper, used as the *paper-faithful* backend.
- ``subspace``: blocked subspace (power) iteration — for huge D where the
              D×D Gram does not fit.

All backends return an orthonormal ``(D, m)`` matrix of directions.
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro.core.exact import CERTIFIED_PRECISION

PCAMethod = Literal["gram", "rsvd", "subspace"]

__all__ = [
    "centroid_direction",
    "default_num_directions",
    "pca_directions",
    "project",
]


def default_num_directions(d: int) -> int:
    """The paper's ``m = floor(sqrt(D))`` (at least 1)."""
    return max(1, int(d**0.5))


def centroid_direction(x: jnp.ndarray, y: jnp.ndarray, *, eps: float = 1e-9) -> jnp.ndarray:
    """Unit vector from centroid(x) to centroid(y); falls back to e_1.

    Alg. 1 lines 1-2.  Works on any float dtype; computes the means in fp32.
    """
    xbar = jnp.mean(x.astype(jnp.float32), axis=0)
    ybar = jnp.mean(y.astype(jnp.float32), axis=0)
    return _normalize_direction(ybar - xbar, eps=eps)


def _normalize_direction(u: jnp.ndarray, *, eps: float = 1e-9) -> jnp.ndarray:
    norm = jnp.linalg.norm(u)
    e1 = jnp.zeros_like(u).at[0].set(1.0)
    return jnp.where(norm < eps, e1, u / jnp.maximum(norm, eps))


def project(points: jnp.ndarray, directions: jnp.ndarray) -> jnp.ndarray:
    """Project ``(n, D)`` points onto ``(D, m)`` directions → ``(n, m)`` scalars.

    fp32 accumulation regardless of input dtype (a projection is the quantity
    whose *order statistics* we select on; bf16 accumulation can swap ranks).
    """
    if directions.ndim == 1:
        directions = directions[:, None]
    return jnp.matmul(
        points, directions,
        precision=CERTIFIED_PRECISION, preferred_element_type=jnp.float32,
    )


# ---------------------------------------------------------------------------
# PCA backends
# ---------------------------------------------------------------------------


def _top_eigvecs_from_gram(gram: jnp.ndarray, m: int) -> jnp.ndarray:
    """Top-m eigenvectors of a symmetric PSD matrix, descending eigenvalue."""
    w, v = jnp.linalg.eigh(gram)  # ascending
    return v[:, ::-1][:, :m]


def _pca_gram(z: jnp.ndarray, mean: jnp.ndarray, m: int) -> jnp.ndarray:
    zc = z.astype(jnp.float32) - mean
    gram = jnp.matmul(zc.T, zc, preferred_element_type=jnp.float32)
    return _top_eigvecs_from_gram(gram, m)


def _pca_rsvd(
    z: jnp.ndarray,
    mean: jnp.ndarray,
    m: int,
    *,
    key: jax.Array,
    oversample: int = 8,
    power_iters: int = 2,
) -> jnp.ndarray:
    """Randomized range-finder SVD (Halko/Martinsson/Tropp) — paper-faithful
    O(n D m) backend."""
    zc = z.astype(jnp.float32) - mean
    d = zc.shape[1]
    ell = min(d, m + oversample)
    omega = jax.random.normal(key, (d, ell), dtype=jnp.float32)
    ys = zc @ omega  # (n, ell)
    q, _ = jnp.linalg.qr(ys)
    for _ in range(power_iters):
        q, _ = jnp.linalg.qr(zc.T @ q)  # (d, ell)
        q, _ = jnp.linalg.qr(zc @ q)  # (n, ell)
    b = q.T @ zc  # (ell, d)
    _, _, vt = jnp.linalg.svd(b, full_matrices=False)
    return vt[:m].T  # (d, m)


def _pca_subspace(
    z: jnp.ndarray,
    mean: jnp.ndarray,
    m: int,
    *,
    key: jax.Array,
    iters: int = 8,
) -> jnp.ndarray:
    """Blocked subspace iteration on the implicit covariance.

    Never materialises D×D: each step is two tall-skinny matmuls, so it works
    for D where the Gram backend would blow VMEM/HBM.
    """
    d = z.shape[1]
    zc = z.astype(jnp.float32) - mean
    q = jax.random.normal(key, (d, m), dtype=jnp.float32)
    q, _ = jnp.linalg.qr(q)

    def body(q, _):
        aq = zc.T @ (zc @ q)  # (d, m): implicit covariance apply
        q, _ = jnp.linalg.qr(aq)
        return q, None

    q, _ = jax.lax.scan(body, q, None, length=iters)
    return q


def pca_directions(
    z: jnp.ndarray,
    m: int,
    *,
    method: PCAMethod = "gram",
    key: jax.Array | None = None,
    mean: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Top-m principal directions of ``z`` (n, D) → orthonormal (D, m).

    ``mean`` may be passed in when already known (e.g. distributed psum mean);
    otherwise it is computed here.  ``key`` is required for the randomized
    backends.
    """
    if mean is None:
        mean = jnp.mean(z.astype(jnp.float32), axis=0)
    if method == "gram":
        return _pca_gram(z, mean, m)
    if key is None:
        raise ValueError(f"PCA method {method!r} requires a PRNG key")
    if method == "rsvd":
        return _pca_rsvd(z, mean, m, key=key)
    if method == "subspace":
        return _pca_subspace(z, mean, m, key=key)
    raise ValueError(f"unknown PCA method: {method!r}")


@functools.partial(jax.jit, static_argnames=("m", "method"))
def direction_set(
    a: jnp.ndarray,
    b: jnp.ndarray,
    m: int,
    *,
    method: PCAMethod = "gram",
    key: jax.Array | None = None,
) -> jnp.ndarray:
    """Centroid direction + top-m PCA directions, stacked as (D, m+1).

    Column 0 is the centroid direction (the paper's ℓ=0), columns 1..m the
    principal components — matching Ĥ = max_{ℓ=0..m} H_{u^(ℓ)}.
    """
    u0 = centroid_direction(a, b)
    z = jnp.concatenate([a, b], axis=0)
    us = pca_directions(z, m, method=method, key=key)
    return jnp.concatenate([u0[:, None], us], axis=1)
