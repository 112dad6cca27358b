"""Late-interaction corpus and queries with ColBERTv2 shapes.

A copy of ``repro.data.pointclouds.late_interaction_sets`` kept with the
benchmark, so that a change to the program cannot move the yardstick.  It
differs in two ways: it also returns the flat corpus and the passage
lengths, which the float64 reference scans without re-joining 50,000
arrays, and it draws the query pool from a stream of its own, so that the
corpus of a seed does not depend on how many queries a traffic mix asks
for.

Shapes (arXiv:2112.01488): unit-norm D = 128 token vectors; passages of
32–180 vectors, log-normal around 75 (an MS MARCO passage is ~75 word
pieces, ColBERT's doc_maxlen is 180); queries of 32 vectors (ColBERT pads
every query to 32).  Token vectors are ``normalize(c_t + 0.5·g)`` around
the passage's topic centroid ``c_t`` with 50 passages a topic; a query is
drawn from the topic of a random passage.
"""
from __future__ import annotations

import numpy as np

# The shape keys of a configuration file, with ColBERTv2's values.
SHAPE = {"dim": 128, "min_len": 32, "max_len": 180, "mean_len": 75.0,
         "len_sigma": 0.4, "query_len": 32, "sets_per_topic": 50, "noise": 0.5}


def rng(seed: int, stream: int) -> np.random.Generator:
    """Stream ``stream`` of ``seed``: 0 the corpus, 1 the query pool, 2 the
    warm-up queries, 3 the order of arrivals, 4 the checked sample."""
    return np.random.default_rng([seed % (1 << 64), stream])


def corpus(seed: int, n_sets: int, shape: dict = SHAPE):
    """``(flat, lens, centers, topics)``: ``flat`` is the (Σ lens, dim)
    float32 token matrix, passage ``i`` being rows ``cumsum(lens)[i-1]`` up
    to ``cumsum(lens)[i]``."""
    r = rng(seed, 0)
    n_topics = max(1, n_sets // shape["sets_per_topic"])
    centers = r.standard_normal((n_topics, shape["dim"]), dtype=np.float32)
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    lens = np.exp(r.normal(np.log(shape["mean_len"]), shape["len_sigma"], size=n_sets))
    lens = np.clip(np.rint(lens), shape["min_len"], shape["max_len"]).astype(np.int64)
    topics = r.integers(0, n_topics, size=n_sets)
    flat = _tokens(r, centers, np.repeat(topics, lens), shape["noise"])
    return flat, lens, centers, topics


def queries(seed: int, n_queries: int, centers, topics, shape: dict = SHAPE,
            stream: int = 1) -> np.ndarray:
    """(n_queries, query_len, dim) float32: each from the topic of a random
    passage, from a stream apart from the corpus's."""
    r = rng(seed, stream)
    q_topics = topics[r.integers(0, topics.size, size=n_queries)]
    rows = np.repeat(q_topics, shape["query_len"])
    return _tokens(r, centers, rows, shape["noise"]).reshape(
        n_queries, shape["query_len"], centers.shape[1])


def _tokens(r, centers, topic_of_row, noise: float) -> np.ndarray:
    dim = centers.shape[1]
    x = r.standard_normal((topic_of_row.size, dim), dtype=np.float32)
    x *= noise / np.sqrt(dim)
    x += centers[topic_of_row]
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    return x


def split(flat: np.ndarray, lens: np.ndarray) -> list[np.ndarray]:
    """The passages as a list of (len, 128) views of ``flat``."""
    return np.split(flat, np.cumsum(lens)[:-1])
