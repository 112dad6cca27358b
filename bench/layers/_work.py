"""Work models of the kernels, from shapes and counts, never from timings,
and the names by which the trace reduction finds each kernel.

A roofline share is the least time the chip could take for the work,
``max(flops / peak FLOP/s, bytes / peak B/s)``, over the kernel's device
time.  The peaks are bf16's: at HIGHEST (about six bf16 passes) a
compute-bound float32 kernel cannot read much above a sixth of them.
"""
from __future__ import annotations

import math

import numpy as np

# Substrings of the kernels' operation names as the trace prints them.
MQ_KERNEL = "multiquery"
FUSED_KERNEL = "fused_min_sqdists"

F32 = 4


def is_mq(name: str) -> bool:
    return MQ_KERNEL in name


def is_fused(name: str) -> bool:
    return FUSED_KERNEL in name


def shortest_points(lens_cumsum: np.ndarray, count: float) -> float:
    """Points in the ``count`` shortest sets, interpolated between whole
    sets: a floor under the points of any ``count`` sets."""
    if count <= 0:
        return 0.0
    count = min(count, lens_cumsum.size)
    lo = math.floor(count)
    base = float(lens_cumsum[lo - 1]) if lo else 0.0
    if lo < lens_cumsum.size and count > lo:
        nxt = float(lens_cumsum[lo]) - base
        base += (count - lo) * nxt
    return base


def bucket_flush(pairs: int, unique_queries: int, work: dict) -> tuple[float, float]:
    """``(flops, bytes)`` of one flush's stage-2a launches: ``2·D·|q|·|s|``
    for every (query, set) pair handed to the kernel, at true lengths, and
    the handed sets' points read once plus the queries.  Only the count of
    pairs is known, so each query's pairs are taken as the shortest sets:
    exact where every set is handed, a floor otherwise (the sum of the
    ``c`` shortest lengths is convex in ``c``)."""
    if unique_queries <= 0 or pairs <= 0:
        return 0.0, 0.0
    d, nq, cum = work["dim"], work["query_len"], work["lens_cumsum"]
    per_query = shortest_points(cum, pairs / unique_queries)
    flops = 2.0 * d * nq * unique_queries * per_query
    nbytes = F32 * d * (per_query + unique_queries * nq)
    return flops, nbytes


def fused_distance(n_sel_a: int, n_sel_b: int, n_a: int, n_b: int, d: int) -> tuple[float, float]:
    """``(flops, bytes)`` of ProHD's ``inner="full"`` nearest-neighbour
    scans: ``2·D·(n_sel_a·n_b + n_sel_b·n_a)``, every operand read once."""
    flops = 2.0 * d * (n_sel_a * n_b + n_sel_b * n_a)
    nbytes = F32 * d * (n_sel_a + n_b + n_sel_b + n_a)
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    return max(flops / peaks["flops_per_s"], nbytes / peaks["bytes_per_s"])
