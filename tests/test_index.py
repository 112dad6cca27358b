"""repro.index: SetStore packing/summaries + certified cascade search.

The headline invariant is the certification: ``search()`` top-k ids and
values must be BIT-FOR-BIT identical to brute-force exact ranking, for any
corpus, any k (including ties and k ≥ corpus size), any padding layout.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import masked
from repro.core.exact import hausdorff_dense
from repro.hd import search as hd_search
from repro.index import (
    SetStore,
    bound_scale,
    bucket_capacity,
    certified_margins,
    direction_bank,
    interval_bounds,
    search,
    summarize_set,
)

KEY = jax.random.PRNGKey(0)

# Shared seeded generators (tests/strategies.py): same RandomState stream
# as the historical module-local copies, so every corpus is bit-identical.
from strategies import query_near as _query  # noqa: E402
from strategies import ragged_corpus as _corpus  # noqa: E402


# ---------------------------------------------------------------------------
# SetStore
# ---------------------------------------------------------------------------


def test_bucket_capacity_power_of_two():
    assert bucket_capacity(1) == 8            # min_bucket floor
    assert bucket_capacity(8) == 8
    assert bucket_capacity(9) == 16
    assert bucket_capacity(100) == 128
    assert bucket_capacity(3, min_bucket=2) == 4


def test_store_roundtrip_and_packing():
    sets, _ = _corpus(0)
    store = SetStore(dim=4)
    ids = store.add_many(sets)
    assert ids == list(range(len(sets)))
    assert store.n_sets == len(sets)
    assert store.total_points == sum(s.shape[0] for s in sets)
    for sid, pts in zip(ids, sets):
        np.testing.assert_array_equal(np.asarray(store.get(sid)), pts)
    # every set appears in exactly one bucket, with its padding masked off
    # and its sqnorms +inf-poisoned outside the valid rows
    seen = []
    for cap, bucket in store.packed_buckets().items():
        assert bucket.points.shape[1:] == (cap, 4)
        for row, sid in enumerate(bucket.set_ids):
            n = sets[sid].shape[0]
            assert cap >= n
            np.testing.assert_array_equal(
                np.asarray(bucket.points[row, :n]), sets[sid]
            )
            assert bool(jnp.all(bucket.valid[row, :n]))
            assert not bool(jnp.any(bucket.valid[row, n:]))
            assert bool(jnp.all(jnp.isinf(bucket.sqnorms[row, n:])))
            seen.append(int(sid))
    assert sorted(seen) == ids


def test_store_rejects_bad_sets():
    store = SetStore(dim=3)
    with pytest.raises(ValueError):
        store.add(np.zeros((0, 3), np.float32))     # empty set
    with pytest.raises(ValueError):
        store.add(np.zeros((4, 5), np.float32))     # wrong dim
    with pytest.raises(ValueError):
        search(np.zeros((4, 3), np.float32), store, 1)  # empty store


def test_summaries_match_numpy_reference():
    sets, _ = _corpus(1, n_sets=10)
    store = SetStore(dim=4)
    store.add_many(sets)
    sums = store.summaries()
    dirs = np.asarray(store.directions)
    for sid, pts in enumerate(sets):
        c = pts.mean(axis=0)
        r = np.linalg.norm(pts - c, axis=1)
        proj = pts @ dirs
        np.testing.assert_allclose(np.asarray(sums.centroid[sid]), c, atol=1e-5)
        np.testing.assert_allclose(float(sums.r_min[sid]), r.min(), atol=1e-5)
        np.testing.assert_allclose(float(sums.r_max[sid]), r.max(), atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(sums.proj_lo[sid]), proj.min(axis=0), rtol=1e-4, atol=1e-4
        )
        np.testing.assert_allclose(
            np.asarray(sums.proj_hi[sid]), proj.max(axis=0), rtol=1e-4, atol=1e-4
        )
        assert int(sums.count[sid]) == pts.shape[0]


def test_summarize_is_padding_invariant():
    rng = np.random.RandomState(2)
    pts = rng.randn(11, 4).astype(np.float32)
    dirs = direction_bank(4, 2)
    raw, _ = summarize_set(jnp.asarray(pts), jnp.ones((11,), bool), dirs)
    padded = np.zeros((32, 4), np.float32)
    padded[:11] = pts
    # poison the padding with garbage: summaries must not see it
    padded[11:] = 1e9
    valid = np.zeros((32,), bool)
    valid[:11] = True
    masked_sum, sqn = summarize_set(jnp.asarray(padded), jnp.asarray(valid), dirs)
    for f_raw, f_masked in zip(raw, masked_sum):
        np.testing.assert_allclose(np.asarray(f_raw), np.asarray(f_masked), rtol=1e-6)
    assert bool(jnp.all(jnp.isinf(sqn[11:])))


# ---------------------------------------------------------------------------
# certified bounds
# ---------------------------------------------------------------------------


def test_interval_bounds_contain_true_hd():
    rng = np.random.RandomState(3)
    dirs = direction_bank(6, 3)
    # the 1e5 offset is the catastrophic-cancellation regime: projection
    # gaps of huge-coordinate clouds carry absolute fp32 error far larger
    # than any relative-in-the-gap margin — bound_scale must absorb it
    for trial, offset in [(t, o) for t in range(10) for o in (0.0, 1e5)]:
        a = (rng.randn(rng.randint(1, 30), 6) * rng.choice([0.3, 1.0, 5.0]) + offset).astype(np.float32)
        b = (rng.randn(rng.randint(1, 30), 6) + rng.randn(6) * 4 + offset).astype(np.float32)
        sa, _ = summarize_set(jnp.asarray(a), jnp.ones((a.shape[0],), bool), dirs)
        sb, _ = summarize_set(jnp.asarray(b), jnp.ones((b.shape[0],), bool), dirs)
        h = float(hausdorff_dense(a, b))
        scale = bound_scale(sa, sb)
        lb, ub = certified_margins(*interval_bounds(sa, sb), scale, 6)
        assert float(lb) <= h <= float(ub), (trial, offset, float(lb), h, float(ub))
        # directed bounds against directed truth
        from repro.core.exact import directed_hd_dense

        hd = float(directed_hd_dense(a, b))
        lbd, ubd = certified_margins(*interval_bounds(sa, sb, directed=True), scale, 6)
        assert float(lbd) <= hd <= float(ubd), (trial, offset, float(lbd), hd, float(ubd))


def test_masked_prohd_certificate_contains_truth_and_ignores_padding():
    rng = np.random.RandomState(4)
    a = rng.randn(13, 4).astype(np.float32)
    b = (rng.randn(9, 4) + 3.0).astype(np.float32)

    def padded(x, cap):
        p = np.full((cap, 4), 7.7e8, np.float32)  # garbage padding
        p[: x.shape[0]] = x
        v = np.zeros((cap,), bool)
        v[: x.shape[0]] = True
        return jnp.asarray(p), jnp.asarray(v)

    h = float(hausdorff_dense(a, b))
    certs = []
    for cap_a, cap_b in ((16, 16), (32, 64)):
        pa, va = padded(a, cap_a)
        pb, vb = padded(b, cap_b)
        cert = masked.masked_prohd_certified_jit(pa, va, pb, vb, alpha=0.2, m=2)
        assert float(cert.lower) <= h * (1 + 1e-5) + 1e-6
        assert h <= float(cert.upper) * (1 + 1e-5) + 1e-6
        assert float(cert.hd) <= h * (1 + 1e-5) + 1e-6  # full-inner: never over
        certs.append(cert)
    # the certificate is a function of the valid rows only — padding
    # layouts agree up to fp re-association (selection k's differ with
    # capacity, which may move hd; lower/upper are selection-free)
    np.testing.assert_allclose(float(certs[0].lower), float(certs[1].lower), rtol=2e-3)
    np.testing.assert_allclose(float(certs[0].upper), float(certs[1].upper), rtol=2e-3)


# ---------------------------------------------------------------------------
# cascade == brute force
# ---------------------------------------------------------------------------


def _assert_search_matches_bruteforce(sets, q, k, variant="hausdorff", min_bucket=8):
    store = SetStore(dim=q.shape[1], min_bucket=min_bucket)
    store.add_many(sets)
    res = search(q, store, k, variant=variant)
    ref = search(q, store, k, variant=variant, method="exact")
    np.testing.assert_array_equal(res.ids, ref.ids)
    np.testing.assert_array_equal(res.values, ref.values)
    assert res.stats["exact_refines"] <= ref.stats["exact_refines"]
    return res


def test_search_matches_bruteforce_with_duplicates_and_large_k():
    sets, rng = _corpus(5, n_sets=22, dup_every=3)
    q = _query(rng, sets, 4)
    _assert_search_matches_bruteforce(sets, q, 5)
    _assert_search_matches_bruteforce(sets, q, 100)   # k >= corpus size
    _assert_search_matches_bruteforce(sets, q, 5, variant="directed")


def test_search_is_padding_invariant():
    sets, rng = _corpus(6, n_sets=18)
    q = _query(rng, sets, 4)
    results = [
        _assert_search_matches_bruteforce(sets, q, 4, min_bucket=mb)
        for mb in (2, 8, 32)
    ]
    for r in results[1:]:
        np.testing.assert_array_equal(r.ids, results[0].ids)
        np.testing.assert_array_equal(r.values, results[0].values)


def test_search_prunes_separated_corpus():
    from repro.data.pointclouds import clustered_sets

    sets, _ = clustered_sets(
        jax.random.PRNGKey(7), 64, 4, sizes=(8, 16), n_clusters=8, spread=20.0
    )
    rng = np.random.RandomState(8)
    q = _query(rng, sets, 4)
    store = SetStore(dim=4)
    store.add_many(sets)
    res = search(q, store, 3)
    ref = search(q, store, 3, method="exact")
    np.testing.assert_array_equal(res.ids, ref.ids)
    np.testing.assert_array_equal(res.values, ref.values)
    assert res.stats["prune_fraction"] > 0.5
    assert res.stats["exact_refines"] < 32


def test_stage1_launches_split_by_lane_budget(monkeypatch):
    """A bucket wider than stage 1's per-launch element budget runs as
    several equal-width launches; the top-k is unchanged and every
    frontier lane still gets its certificate."""
    from repro.index import cascade

    sets, rng = _corpus(11, n_sets=40)
    q = _query(rng, sets, 4)
    store = SetStore(dim=4)
    store.add_many(sets)
    whole = search(q, store, 4)
    calls = []
    real = cascade._stage1_batch

    def spy(qq, pts, valid, **kw):
        calls.append(pts.shape[0])
        return real(qq, pts, valid, **kw)

    monkeypatch.setattr(cascade, "_stage1_batch", spy)
    cap = max(store.bucket_capacities)
    monkeypatch.setattr(cascade, "_STAGE1_LAUNCH_ELEMS", 2 * (q.shape[0] + cap) * 4)
    split = search(q, store, 4)
    np.testing.assert_array_equal(split.ids, whole.ids)
    np.testing.assert_array_equal(split.values, whole.values)
    assert split.stats["stage1_pruned"] == whole.stats["stage1_pruned"]
    assert 2 in calls and len(calls) > len(store.bucket_capacities)


def test_front_door_search_is_the_cascade():
    sets, rng = _corpus(9, n_sets=12)
    q = _query(rng, sets, 4)
    store = SetStore(dim=4)
    store.add_many(sets)
    res = hd_search(q, store, 3, measure=True)
    ref = search(q, store, 3)
    np.testing.assert_array_equal(res.ids, ref.ids)
    np.testing.assert_array_equal(res.values, ref.values)
    assert res.meta.variant == "hausdorff"
    assert res.meta.method == "cascade"
    assert res.meta.elapsed_s is not None
    assert {"candidates_scanned", "exact_refines", "prune_fraction"} <= set(res.stats)


def test_search_validates_axes():
    sets, rng = _corpus(10, n_sets=4)
    store = SetStore(dim=4)
    store.add_many(sets)
    q = _query(rng, sets, 4)
    with pytest.raises(ValueError):
        search(q, store, 1, variant="chamfer")
    with pytest.raises(ValueError):
        search(q, store, 1, method="prohd")
    with pytest.raises(ValueError):
        search(q, store, -1)            # k=0 is now a valid empty request
    with pytest.raises(ValueError):
        search(q, store, 1, stage2="vectorized")
    with pytest.raises(ValueError):
        search(q[:, :2], store, 1)


def test_search_matches_bruteforce_on_large_magnitude_corpus():
    # coordinates ~1e5: certification must survive fp32 cancellation in
    # every stage's bounds (regression for the scale-aware margins)
    sets, rng = _corpus(14, n_sets=20, dup_every=4)
    sets = [s + np.float32(1e5) for s in sets]
    q = _query(rng, sets, 4)
    _assert_search_matches_bruteforce(sets, q, 4)


def test_interleaved_add_search_repacks_only_the_touched_bucket():
    sets, rng = _corpus(15, n_sets=12, max_n=7)   # all land in the 8-bucket
    store = SetStore(dim=4)
    store.add_many(sets)
    q = _query(rng, sets, 4)
    search(q, store, 2)
    before = store.packed_buckets()
    store.add(np.zeros((30, 4), np.float32) + 50.0)  # lands in the 32-bucket
    res = search(q, store, 2)
    after = store.packed_buckets()
    # the untouched 8-bucket's device arrays were reused, not re-stacked
    assert after[8].points is before[8].points
    assert set(after) == {8, 32}
    ref = search(q, store, 2, method="exact")
    np.testing.assert_array_equal(res.ids, ref.ids)
    np.testing.assert_array_equal(res.values, ref.values)


def test_masked_projected_hd_empty_target_side_is_zero():
    pa = jnp.asarray(np.random.RandomState(0).randn(6, 2), jnp.float32)
    va = jnp.ones((6,), bool)
    pb = jnp.full((4, 2), 123.0, jnp.float32)
    vb = jnp.zeros((4,), bool)  # no valid targets at all
    assert float(masked.masked_projected_hd(pa, va, pb, vb, directed=True)) == 0.0


# Deterministic sweep of the same property the hypothesis module
# (tests/test_index_properties.py) hunts adversarially — keeps the
# certification exercised even where hypothesis is not installed.
@pytest.mark.parametrize("seed,k,dup_every", [(11, 1, 0), (12, 3, 3), (13, 1000, 2)])
def test_cascade_identical_to_bruteforce_seeded(seed, k, dup_every):
    sets, rng = _corpus(seed, n_sets=16, d=4, max_n=14, dup_every=dup_every)
    q = _query(rng, sets, 4)
    _assert_search_matches_bruteforce(sets, q, k)


# ---------------------------------------------------------------------------
# batched stage 2 (PR 4 tentpole)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed,k,variant", [(21, 3, "hausdorff"), (22, 1000, "hausdorff"), (23, 4, "directed")])
def test_stage2_batched_and_sequential_identical(seed, k, variant):
    """Both stage-2 dispatch modes return the SAME BITS as brute force —
    batching tightens bounds, it never touches a returned value."""
    sets, rng = _corpus(seed, n_sets=20, d=4, max_n=18, dup_every=4)
    q = _query(rng, sets, 4)
    store = SetStore(dim=4)
    store.add_many(sets)
    bat = search(q, store, k, variant=variant, stage2="batched")
    seq = search(q, store, k, variant=variant, stage2="sequential")
    ref = search(q, store, k, variant=variant, method="exact")
    for res in (bat, seq):
        np.testing.assert_array_equal(res.ids, ref.ids)
        np.testing.assert_array_equal(res.values, ref.values)
    assert bat.stats["stage2_mode"] == "batched"
    assert seq.stats["stage2_mode"] == "sequential"
    # one dispatch per frontier candidate sequentially…
    assert seq.stats["stage2_calls"] == seq.stats["exact_refines"]
    # …while batching only ever raw-refines a subset of that frontier
    assert bat.stats["exact_refines"] <= seq.stats["exact_refines"]


def test_stage2_batched_raw_refines_only_the_boundary():
    """On an overlapping corpus (stage 0/1 can barely prune, so the whole
    corpus reaches stage 2) the batched mode measures the ENTIRE frontier
    in O(buckets) jitted calls and raw-refines only the ≈ k candidates
    whose ±fp_margin intervals straddle the top-k boundary — while the
    sequential mode pays one dispatch per candidate it inspects."""
    sets, rng = _corpus(24, n_sets=60, d=8, max_n=25, n_clusters=1, spread=0.5)
    q = _query(rng, sets, 8)
    store = SetStore(dim=8)
    store.add_many(sets)
    k = 3
    bat = search(q, store, k, stage2="batched")
    seq = search(q, store, k, stage2="sequential")
    ref = search(q, store, k, method="exact")
    for res in (bat, seq):
        np.testing.assert_array_equal(res.ids, ref.ids)
        np.testing.assert_array_equal(res.values, ref.values)
    # the overlapping regime floods stage 2a with (almost) the whole corpus…
    assert bat.stats["stage2_batched_candidates"] > 3 * k
    # …which the batched pass absorbs in O(buckets) calls, leaving only the
    # boundary for raw per-candidate dispatch — never more than sequential
    assert bat.stats["exact_refines"] <= k + 2
    assert bat.stats["exact_refines"] <= seq.stats["exact_refines"]
    assert seq.stats["exact_refines"] > k
    n_buckets = len(store.bucket_capacities)
    assert bat.stats["stage2_calls"] <= 2 * n_buckets + bat.stats["exact_refines"]
    assert bat.stats["stage2_distinct_shapes"] <= n_buckets + bat.stats["exact_refines"]


def test_slot_index_tracks_packed_buckets():
    sets, _ = _corpus(25, n_sets=12)
    store = SetStore(dim=4)
    store.add_many(sets)
    slot = store.slot_index()
    buckets = store.packed_buckets()
    assert sorted(slot) == list(range(len(sets)))
    for sid, (cap, row) in slot.items():
        assert int(buckets[cap].set_ids[row]) == sid
    # grows with the store, including for fresh capacities
    sid = store.add(np.full((40, 4), 3.0, np.float32))
    cap, row = store.slot_index()[sid]
    assert cap == 64 and int(store.packed_buckets()[64].set_ids[row]) == sid


def test_slot_index_unknown_ids_are_absent():
    """slot_index() is a plain {known id: slot} dict — ids never stored
    (including negative and past-the-end ones) are ABSENT, so a stale or
    corrupted id raises KeyError instead of silently aliasing a slab row."""
    sets, _ = _corpus(26, n_sets=5)
    store = SetStore(dim=4)
    store.add_many(sets)
    slot = store.slot_index()
    for bogus in (-1, len(sets), len(sets) + 7, 10**6):
        assert bogus not in slot
        with pytest.raises(KeyError):
            slot[bogus]
    # the index is a snapshot: mutating the returned dict must not corrupt
    # the store's cached copy
    slot[-1] = (999, 0)
    assert -1 not in store.slot_index()


def test_search_on_empty_store_raises():
    store = SetStore(dim=4)
    q = np.zeros((3, 4), np.float32)
    with pytest.raises(ValueError, match="empty SetStore"):
        search(q, store, 1)
    # k == 0 is the one degenerate request served without a corpus scan —
    # but an empty store still has nothing to serve it from
    with pytest.raises(ValueError, match="empty SetStore"):
        search(q, store, 0)


def test_single_all_padded_slab_lane_conventions():
    """A bucket whose ONE slab lane is entirely padding (no valid row) —
    the store itself can never produce it (empty sets are rejected), but
    batched consumers can meet it via degenerate gathers.  Every backend
    must finalize it by the empty-side conventions, not garbage."""
    pts = jnp.asarray(np.full((1, 8, 3), 7.7e8, np.float32))  # garbage fill
    valid = jnp.zeros((1, 8), bool)
    q = jnp.asarray(np.random.RandomState(0).randn(5, 3).astype(np.float32))
    for be in sorted(masked.EXACT_MASKED_BACKENDS):
        vals = np.asarray(
            masked.masked_exact_hd_batched(
                q, pts, valid_slab=valid, directed=True, backend=be,
                block_a=64, block_b=64,
            )
        )
        assert vals.shape == (1,) and np.isinf(vals[0]), be  # empty target
        undirected = np.asarray(
            masked.masked_exact_hd_batched(
                q, pts, valid_slab=valid, backend=be, block_a=64, block_b=64
            )
        )
        assert np.isinf(undirected[0]), be


# ---------------------------------------------------------------------------
# direction banks (satellite: data-driven banks)
# ---------------------------------------------------------------------------


from strategies import anisotropic_corpus as _anisotropic_corpus  # noqa: E402


def test_direction_bank_orthonormal_and_deterministic():
    key = jax.random.PRNGKey(5)
    for bank in (
        direction_bank(16, 4, key=key),
        direction_bank(16, 4, data=jnp.asarray(np.random.RandomState(1).randn(64, 16), jnp.float32)),
        direction_bank(3, 7),     # m > d clamps to d
    ):
        b = np.asarray(bank)
        assert b.shape[0] in (16, 3) and b.shape[1] <= b.shape[0]
        np.testing.assert_allclose(b.T @ b, np.eye(b.shape[1]), atol=1e-5)
    # deterministic: same seed → same bits; different seed → different bank
    np.testing.assert_array_equal(
        np.asarray(direction_bank(16, 4, key=key)),
        np.asarray(direction_bank(16, 4, key=jax.random.PRNGKey(5))),
    )
    assert not np.array_equal(
        np.asarray(direction_bank(16, 4, key=key)),
        np.asarray(direction_bank(16, 4, key=jax.random.PRNGKey(6))),
    )
    data = jnp.asarray(np.random.RandomState(2).randn(128, 16), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(direction_bank(16, 4, data=data)),
        np.asarray(direction_bank(16, 4, data=data)),
    )


def test_data_driven_bank_tightens_stage0_lower_bounds():
    """On an anisotropic corpus, PCA directions capture the separation axis
    a random bank mostly misses — stage-0 interval-gap lower bounds must
    come out strictly tighter (ROADMAP: 'nothing refits yet')."""
    sets, rng = _anisotropic_corpus(30)
    q = (np.asarray(sets[0]) + 0.0).astype(np.float32)
    sample = np.concatenate(sets)

    def stage0_lbs(directions):
        store = SetStore(dim=16, directions=directions)
        store.add_many(sets)
        qsum = store.summarize(q)
        lb, _ = interval_bounds(qsum, store.summaries())
        return np.asarray(lb, np.float64)

    lb_rand = stage0_lbs(direction_bank(16, 4, key=jax.random.PRNGKey(0)))
    lb_pca = stage0_lbs(direction_bank(16, 4, data=jnp.asarray(sample)))
    # sound either way (never above the true distance)…
    for sid, pts in enumerate(sets):
        h = float(hausdorff_dense(jnp.asarray(q), jnp.asarray(pts)))
        assert lb_pca[sid] <= h + 1e-3 and lb_rand[sid] <= h + 1e-3
    # …but the data-driven bank is decisively tighter in aggregate
    assert lb_pca.mean() > 1.5 * lb_rand.mean()
    # …and the cascade stays brute-force-identical under a data-driven bank
    store = SetStore(dim=16, directions=direction_bank(16, 4, data=jnp.asarray(sample)))
    store.add_many(sets)
    q2 = _query(rng, sets, 16)
    res = search(q2, store, 3)
    ref = search(q2, store, 3, method="exact")
    np.testing.assert_array_equal(res.ids, ref.ids)
    np.testing.assert_array_equal(res.values, ref.values)
