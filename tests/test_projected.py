"""The certified lower bound's 1-D Hausdorff distances (core/projected.py).

Each direction's distance comes from one sort of the merged projections and
two doubling scans.  Every point is measured against the same nearest
element of the other set as a brute force would pick, by the same float32
subtraction, so the results are checked for exact equality.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.projected import directed_hd_1d, hd_1d, projected_hd


def _brute_directed(pa: np.ndarray, pb: np.ndarray) -> np.float32:
    """max_i min_j |pa_i - pb_j| in float32."""
    return np.abs(pa[:, None] - pb[None, :]).min(axis=1).max()


def _case(kind: str):
    """``(proj_a, proj_b)`` float32 of shapes (n_a, m), (n_b, m)."""
    rng = np.random.RandomState(sum(map(ord, kind)))
    n_a, n_b, m = 37, 23, 3
    if kind == "one_point_a":
        n_a = 1
    elif kind == "one_point_b":
        n_b = 1
    elif kind == "one_point_each":
        n_a = n_b = 1
    elif kind == "m1":
        m = 1
    elif kind == "m17":
        n_a, n_b, m = 300, 211, 17
    pa = rng.randn(n_a, m)
    pb = rng.randn(n_b, m) * 2.0 + 0.5
    if kind == "duplicates":
        pa = np.repeat(pa[:9], 4, axis=0)
        pb = np.repeat(pb[:6], 4, axis=0)
    elif kind == "ties_across_sets":
        # Few distinct values, most of them in both sets.
        pa = rng.randint(-4, 5, (n_a, m)) * 0.25
        pb = rng.randint(-6, 3, (n_b, m)) * 0.25
    elif kind == "negative":
        pa, pb = -np.abs(pa) - 3.0, -np.abs(pb) - 1.0
    return pa.astype(np.float32), pb.astype(np.float32)


CASES = ["unequal_sizes", "one_point_a", "one_point_b", "one_point_each", "duplicates",
         "ties_across_sets", "negative", "m1", "m17"]


@pytest.mark.parametrize("kind", CASES)
def test_matches_brute_force_exactly(kind):
    pa, pb = _case(kind)
    directed, undirected = jax.jit(directed_hd_1d), jax.jit(hd_1d)
    per_dir = []
    for c in range(pa.shape[1]):
        ab = _brute_directed(pa[:, c], pb[:, c])
        ba = _brute_directed(pb[:, c], pa[:, c])
        assert directed(pa[:, c], pb[:, c]) == ab
        assert directed(pb[:, c], pa[:, c]) == ba
        assert undirected(pa[:, c], pb[:, c]) == max(ab, ba)
        per_dir.append(max(ab, ba))
    assert projected_hd(pa, pb) == max(per_dir)


def test_lowered_lower_bound_has_no_loop_or_gather():
    """One sort and elementwise passes: no binary search's ``while`` loop of
    gathers comes back."""
    text = projected_hd.lower(jnp.zeros((64, 17)), jnp.zeros((48, 17))).as_text()
    assert "sort" in text
    assert "while" not in text
    assert "gather" not in text
