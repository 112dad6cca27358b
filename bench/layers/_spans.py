"""Span arithmetic shared by the per-layer readers."""
from __future__ import annotations


def named(spans: list[dict], name: str) -> list[dict]:
    return [s for s in spans if s["name"] == name]


def self_seconds(spans: list[dict], name: str) -> list[float]:
    """Self time of every span called ``name``: its duration less what its
    direct children cover."""
    child: dict[int, float] = {}
    for s in spans:
        if s.get("parent_id") is not None:
            child[s["parent_id"]] = child.get(s["parent_id"], 0.0) + s["dur_s"]
    return [s["dur_s"] - child.get(s["span_id"], 0.0) for s in named(spans, name)]


def mean(xs) -> float | None:
    xs = list(xs)
    return sum(xs) / len(xs) if xs else None
