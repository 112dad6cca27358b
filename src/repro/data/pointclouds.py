"""Point-cloud generators for the paper's evaluation datasets.

Real MNIST/CIFAR/Higgs are not fetchable offline; we generate statistically
matched proxies (documented in DESIGN.md §6) plus the paper's own synthetic
"Random Clouds" spec, which IS exact: uniform in [0,1]^D with a 0.1 offset
between the clouds (§III-A).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "random_clouds",
    "gaussian_mixture_pca",
    "higgs_like",
    "make_dataset",
    "clustered_sets",
    "late_interaction_sets",
]


def random_clouds(key: jax.Array, n_a: int, n_b: int, d: int, *, offset: float = 0.1, dtype=jnp.float32):
    """Paper §III-A: uniform in the unit cube, B offset by +0.1 per coord."""
    ka, kb = jax.random.split(key)
    a = jax.random.uniform(ka, (n_a, d), dtype=dtype)
    b = jax.random.uniform(kb, (n_b, d), dtype=dtype) + offset
    return a, b


def gaussian_mixture_pca(
    key: jax.Array,
    n_a: int,
    n_b: int,
    d: int,
    *,
    n_modes: int = 10,
    spread: float = 4.0,
    decay: float = 0.85,
    dtype=jnp.float32,
):
    """MNIST/CIFAR-after-PCA proxy: anisotropic Gaussian mixture.

    Image embeddings after PCA have (a) multi-modal class clusters and (b)
    a fast-decaying spectrum; both matter for ProHD (PCA directions carry
    most of the spread, which is why the paper's error collapses with D).
    """
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    scales = decay ** jnp.arange(d, dtype=jnp.float32)  # decaying spectrum
    centers_a = jax.random.normal(k1, (n_modes, d)) * spread * scales
    centers_b = jax.random.normal(k2, (n_modes, d)) * spread * scales
    ca = jax.random.randint(k3, (n_a,), 0, n_modes)
    cb = jax.random.randint(k4, (n_b,), 0, n_modes)
    na_noise, nb_noise = jax.random.split(k5)
    a = centers_a[ca] + jax.random.normal(na_noise, (n_a, d)) * scales
    b = centers_b[cb] + jax.random.normal(nb_noise, (n_b, d)) * scales
    return a.astype(dtype), b.astype(dtype)


def higgs_like(key: jax.Array, n_a: int, n_b: int, *, d: int = 28, dtype=jnp.float32):
    """Higgs proxy: two overlapping anisotropic clouds at D=28 (signal vs
    background share most of the feature space; tails differ)."""
    k1, k2, k3 = jax.random.split(key, 3)
    mixing = jax.random.normal(k1, (d, d)) / jnp.sqrt(d)
    a = jax.random.normal(k2, (n_a, d)) @ mixing
    shift = jnp.concatenate([jnp.full((d // 4,), 0.8), jnp.zeros((d - d // 4,))])
    b = jax.random.normal(k3, (n_b, d)) @ mixing * 1.15 + shift
    return a.astype(dtype), b.astype(dtype)


def clustered_sets(
    key: jax.Array,
    n_sets: int,
    d: int,
    *,
    sizes: tuple[int, ...] = (64, 128, 256),
    n_clusters: int = 32,
    spread: float = 10.0,
    sigma: float = 0.5,
):
    """Separated-clusters CORPUS: ``n_sets`` ragged point sets for retrieval.

    Each set is a Gaussian blob (σ = ``sigma``) around one of ``n_clusters``
    well-separated centers (N(0, spread²) per coordinate), with its size
    drawn from ``sizes``.  The separation is the regime the paper's
    vector-DB story targets — and the one where the index cascade's
    certified bounds actually prune (sets in far clusters are resolved
    from summaries alone).

    Returns ``(sets, labels)``: a list of (n_i, d) float32 numpy arrays and
    an (n_sets,) int array of cluster assignments.  Host-side numpy by
    design — corpus construction is data loading, not accelerator work.
    """
    import numpy as np

    seed = int(jax.random.randint(key, (), 0, 2**31 - 1))
    rng = np.random.RandomState(seed)
    centers = rng.randn(n_clusters, d).astype(np.float32) * spread
    labels = rng.randint(0, n_clusters, size=n_sets)
    sets = []
    for i in range(n_sets):
        n = int(rng.choice(sizes))
        pts = centers[labels[i]] + rng.randn(n, d).astype(np.float32) * sigma
        sets.append(pts)
    return sets, labels


def late_interaction_sets(seed: int, n_sets: int, n_queries: int):
    """Late-interaction CORPUS with ColBERTv2 shapes (arXiv:2112.01488).

    Each set is one passage: 32–180 unit-norm 128-dim token vectors
    (ColBERT's doc_maxlen is 180), lengths log-normal around 75 (an MS
    MARCO passage is ~75 word pieces).  Each query is 32 unit vectors
    (ColBERT pads every query to 32).  Token vectors are
    ``normalize(c_t + 0.5 · g)`` around the passage's topic centroid
    ``c_t``, with ``g`` of unit expected norm and 50 passages a topic; a
    query is drawn from the topic of a random passage, so its nearest sets
    are that topic's passages.

    Returns ``(sets, queries)``: lists of float32 numpy arrays.  Generated
    in bulk from one ``numpy`` Generator, so corpus construction costs a
    few vectorised passes, not one draw per set.
    """
    import numpy as np

    d, min_len, max_len, mean_len, query_len = 128, 32, 180, 75.0, 32
    sets_per_topic, noise = 50, 0.5

    rng = np.random.default_rng(seed)
    n_topics = max(1, n_sets // sets_per_topic)
    centers = rng.standard_normal((n_topics, d), dtype=np.float32)
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    lens = np.exp(rng.normal(np.log(mean_len), 0.4, size=n_sets))
    lens = np.clip(np.rint(lens), min_len, max_len).astype(np.int64)
    topics = rng.integers(0, n_topics, size=n_sets)
    q_topics = topics[rng.integers(0, n_sets, size=n_queries)]

    def tokens(topic_of_row):
        x = rng.standard_normal((topic_of_row.size, d), dtype=np.float32)
        x *= noise / np.sqrt(d)
        x += centers[topic_of_row]
        x /= np.linalg.norm(x, axis=1, keepdims=True)
        return x

    corpus = tokens(np.repeat(topics, lens))
    sets = np.split(corpus, np.cumsum(lens)[:-1])
    queries = np.split(tokens(np.repeat(q_topics, query_len)), n_queries)
    return sets, queries


def make_dataset(name: str, key: jax.Array, n_a: int, n_b: int, d: int, **kw):
    """Dataset factory used by benchmarks: 'random' | 'image' | 'higgs'."""
    if name == "random":
        return random_clouds(key, n_a, n_b, d, **kw)
    if name == "image":
        return gaussian_mixture_pca(key, n_a, n_b, d, **kw)
    if name == "higgs":
        return higgs_like(key, n_a, n_b, d=d, **kw)
    raise ValueError(f"unknown dataset {name!r}")
