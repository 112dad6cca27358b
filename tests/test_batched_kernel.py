"""Unit sweep for the batched masked bucket kernel
(``repro.kernels.hausdorff.batched``) — the slab-granularity analogue of
``test_kernels``' single-pair checks.

CPU runs the kernel in interpret mode (the explicit-backend testing path);
the ``pallas``-marked native test compiles the same launch on TPU and
skips cleanly elsewhere.  The conformance harness (``tests/conformance/``)
owns the padded-vs-raw/margin contract for the REGISTERED backend views;
this module pins the kernel-level mechanics: both accumulators against the
dense oracle, gate semantics, pow2-pad-lane skips, and slab layout edges.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import exact
from repro.index import fp_value_margin
from repro.kernels.hausdorff import batched

import strategies


def _slab(seed=0, batch=5, cap=16, d=5, nq=9):
    return strategies.bucket_case(seed, batch=batch, cap=cap, d=d, nq=nq)


@pytest.mark.parametrize("use_pallas", [True, False], ids=["pallas", "mirror"])
@pytest.mark.parametrize("directed", [False, True], ids=["H", "h"])
def test_both_accumulators_match_dense_oracle(use_pallas, directed):
    q, raws, pts, val = _slab()
    vals = np.asarray(
        batched.batched_bucket_hd(
            q, pts, valid_slab=val, directed=directed,
            block_a=64, block_b=64, use_pallas=use_pallas,
        ),
        np.float64,
    )
    qn = float(np.linalg.norm(np.asarray(q), axis=1).max())
    for i, raw in enumerate(raws):
        if directed:
            want = float(exact.directed_hd_dense(q, jnp.asarray(raw)))
        else:
            want = float(exact.hausdorff_dense(q, jnp.asarray(raw)))
        scale = qn + float(np.linalg.norm(raw, axis=1).max())
        margin = float(fp_value_margin(5, scale, vals[i]))
        assert abs(vals[i] - want) <= margin, (use_pallas, directed, i)


@pytest.mark.parametrize("use_pallas", [True, False], ids=["pallas", "mirror"])
def test_min_vectors_expose_both_directions(use_pallas):
    """The raw (min_a, min_b) vectors — not just the finalized scalar —
    agree with the dense squared-distance matrix per lane."""
    q, raws, pts, val = _slab(seed=3, batch=3, cap=8, d=4, nq=6)
    mina, minb = batched.batched_min_sqdists(
        q, pts, valid_slab=val, block_a=64, block_b=64, use_pallas=use_pallas
    )
    mina, minb = np.asarray(mina, np.float64), np.asarray(minb, np.float64)
    for i, raw in enumerate(raws):
        d2 = np.asarray(exact.pairwise_sqdist(q, jnp.asarray(raw)), np.float64)
        n = raw.shape[0]
        np.testing.assert_allclose(mina[i], d2.min(axis=1), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(minb[i, :n], d2.min(axis=0), rtol=1e-5, atol=1e-5)
        assert np.isinf(minb[i, n:]).all()  # padded rows stay poisoned


def test_interpret_slab_reorder_is_bitwise():
    """Permuting slab lanes permutes results bitwise (set-slot grid axis
    carries no cross-lane state)."""
    q, _, pts, val = _slab(seed=5, batch=7)
    base = np.asarray(
        batched.batched_bucket_hd(q, pts, valid_slab=val, block_a=64, block_b=64)
    )
    perm = np.random.RandomState(1).permutation(7)
    got = np.asarray(
        batched.batched_bucket_hd(
            q, pts[perm], valid_slab=val[perm], block_a=64, block_b=64
        )
    )
    np.testing.assert_array_equal(got, base[perm])


@pytest.mark.parametrize("use_pallas", [True, False], ids=["pallas", "mirror"])
def test_gate_skips_exactly_lb_above_cut(use_pallas):
    q, _, pts, val = _slab(seed=7, batch=6)
    base = np.asarray(
        batched.batched_bucket_hd(
            q, pts, valid_slab=val, block_a=64, block_b=64, use_pallas=use_pallas
        )
    )
    lb = jnp.asarray([0.0, 9.0, 0.0, 9.0, 0.0, 9.0], jnp.float32)
    cut = jnp.full((6,), 1.0, jnp.float32)
    got = np.asarray(
        batched.batched_bucket_hd(
            q, pts, valid_slab=val, lb=lb, cut=cut,
            block_a=64, block_b=64, use_pallas=use_pallas,
        )
    )
    skip = np.asarray(lb) > np.asarray(cut)
    assert np.isinf(got[skip]).all()
    np.testing.assert_array_equal(got[~skip], base[~skip])


@pytest.mark.parametrize("use_pallas", [True, False], ids=["pallas", "mirror"])
def test_pow2_pad_lanes_ride_with_inf_lb(use_pallas):
    """The cascade's pad-lane discipline: duplicates appended to reach a
    pow2 batch are gated out with lb = +inf and must come back +inf while
    the real lanes keep their gate-off bits."""
    q, _, pts, val = _slab(seed=9, batch=3)
    pts8 = jnp.concatenate([pts, jnp.tile(pts[:1], (5, 1, 1))])
    val8 = jnp.concatenate([val, jnp.tile(val[:1], (5, 1))])
    lb = jnp.asarray([0.0] * 3 + [np.inf] * 5, jnp.float32)
    cut = jnp.full((8,), 1e30, jnp.float32)
    base = np.asarray(
        batched.batched_bucket_hd(
            q, pts, valid_slab=val, block_a=64, block_b=64, use_pallas=use_pallas
        )
    )
    got = np.asarray(
        batched.batched_bucket_hd(
            q, pts8, valid_slab=val8, lb=lb, cut=cut,
            block_a=64, block_b=64, use_pallas=use_pallas,
        )
    )
    np.testing.assert_array_equal(got[:3], base)
    assert np.isinf(got[3:]).all()


@pytest.mark.parametrize("use_pallas", [True, False], ids=["pallas", "mirror"])
def test_multi_tile_grid_matches_single_tile(use_pallas):
    """Slabs spanning several (i, j) tiles reduce to the same values as a
    one-tile launch (min folding across the grid is exact)."""
    q, raws, pts, val = _slab(seed=11, batch=3, cap=96, d=4, nq=50)
    one = np.asarray(
        batched.batched_bucket_hd(
            q, pts, valid_slab=val, block_a=128, block_b=128,
            use_pallas=use_pallas,
        )
    )
    tiled = np.asarray(
        batched.batched_bucket_hd(
            q, pts, valid_slab=val, block_a=16, block_b=32,
            use_pallas=use_pallas,
        )
    )
    np.testing.assert_array_equal(tiled, one)


def test_vmapped_single_pair_view_equals_native_slab():
    """The registered single-pair adapters vmap back into a batched grid:
    vmapping the S=1 view over the slab must equal the native S-lane call
    bitwise (same kernel, same tile shapes)."""
    q, _, pts, val = _slab(seed=13, batch=6)
    native = np.asarray(
        batched.batched_bucket_hd(q, pts, valid_slab=val, block_a=64, block_b=64)
    )
    vmapped = np.asarray(
        jax.vmap(
            lambda p, v: batched.batched_bucket_hd(
                q, p[None], valid_slab=v[None], block_a=64, block_b=64
            )[0]
        )(pts, val)
    )
    np.testing.assert_array_equal(vmapped, native)


@pytest.mark.parametrize("multi", [False, True], ids=["batched", "multiquery"])
def test_gate_lanes_split_across_launches(monkeypatch, multi):
    """A slab with more gate lanes than one launch's SMEM holds runs as
    several launches over consecutive set ranges, bit for bit the values
    of a single launch (eager, so the patched limit is read on each call)."""
    q, raws, pts, val = _slab(seed=5, batch=7, cap=16, d=5, nq=9)
    lb = jnp.asarray(np.arange(7, dtype=np.float32))
    cut = jnp.full((7,), 4.5, jnp.float32)

    def run():
        if multi:
            qs = jnp.stack([q, q[::-1], q * 0.5])
            return batched.multiquery_min_sqdists(
                qs, pts, valid_slab=val, lb=jnp.tile(lb, (3, 1)),
                cut=jnp.tile(cut, (3, 1)), block_a=64, block_b=64,
            )
        return batched.batched_min_sqdists(
            q, pts, valid_slab=val, lb=lb, cut=cut, block_a=64, block_b=64
        )

    with jax.disable_jit():
        whole = run()
        monkeypatch.setattr(batched, "GATE_LANES", 4)
        split = run()
    for w, s in zip(whole, split):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(s))
    assert np.isinf(np.asarray(whole[0])[..., 5:, :]).all()


@pytest.mark.pallas
@pytest.mark.skipif(
    jax.default_backend() != "tpu", reason="native Pallas lowering needs a TPU"
)
def test_native_tpu_launch_matches_interpret():
    """Compiled (non-interpret) launch against the interpret-mode values —
    the TPU half of the certification; the conformance margin covers any
    MXU-vs-XLA contraction drift."""
    q, raws, pts, val = _slab(seed=17, batch=4, cap=256, d=8, nq=128)
    native = np.asarray(
        batched.batched_bucket_hd(
            q, pts, valid_slab=val, block_a=128, block_b=128, interpret=False
        ),
        np.float64,
    )
    interp = np.asarray(
        batched.batched_bucket_hd(
            q, pts, valid_slab=val, block_a=128, block_b=128, interpret=True
        ),
        np.float64,
    )
    qn = float(np.linalg.norm(np.asarray(q), axis=1).max())
    for i, raw in enumerate(raws):
        scale = qn + float(np.linalg.norm(raw, axis=1).max())
        margin = float(fp_value_margin(8, scale, native[i]))
        assert abs(native[i] - interp[i]) <= margin, i
