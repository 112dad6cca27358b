"""chip_smoke.py rehearsed on the CPU at a tiny size.

The phase functions run here exactly as on the chip: the Pallas kernels in
interpret mode and the mirror backends that ``auto`` resolves off-TPU.
This covers their control flow and the float64 comparisons.  The checks
that only a TPU can pass (platform, ``*_pallas`` resolution,
``tpu_custom_call`` in stage 2a) live in ``chip_smoke.run`` and are not
weakened here; the last tests only show that without a TPU, or without
the rest of the repository, the script fails and prints no result.
"""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def corpus(smoke):
    store, sets, queries, facts = smoke.build_store(0, 150, 10)
    ref = smoke.corpus_hd_f64(sets, queries, block_points=1 << 11)
    return store, sets, queries, facts, ref, smoke.pair_scales(sets, queries)


def test_corpus_has_late_interaction_shapes(corpus):
    store, sets, queries, facts, _, _ = corpus
    lens = np.asarray([s.shape[0] for s in sets])
    assert lens.min() >= 32 and lens.max() <= 180 and store.dim == 128
    assert all(q.shape == (32, 128) for q in queries)
    norms = np.linalg.norm(np.concatenate(sets), axis=1)
    assert np.allclose(norms, 1.0, atol=1e-5)
    assert facts["slab_bytes_on_device"] > 0 and facts["sets"] == 150


def test_float64_references_match_direct_formulas(smoke, corpus):
    _, sets, queries, _, ref, _ = corpus
    for qi, si in ((0, 0), (3, 77), (9, 149)):
        q = queries[qi].astype(np.float64)
        s = sets[si].astype(np.float64)
        d = np.sqrt(((q[:, None, :] - s[None, :, :]) ** 2).sum(-1))
        want = max(d.min(axis=1).max(), d.min(axis=0).max())
        assert abs(ref[qi, si] - want) < 1e-12
        assert abs(smoke.hausdorff_f64(q, s, block=7) - want) < 1e-12


def test_precision_phase_reports_every_route(smoke, corpus):
    tokens = np.concatenate(corpus[1][:10])
    facts = smoke.precision_phase(tokens[:64], tokens[64:160])
    for where in ("xla", "pallas"):
        for prec in ("default", "highest"):
            assert 0.0 <= facts[f"{where}_{prec}_d2_err"] <= facts["d2_budget"]


def test_retrieval_phase_checks_every_result(smoke, corpus):
    store, _, queries, _, ref, scales = corpus
    facts = smoke.retrieval_phase(store, queries, ref, scales, k=5, epsilon=0.01)
    assert facts["search_masked_backend"] == "batched_mirror"
    assert facts["engine_masked_backends"] == ["multiquery_mirror"]
    assert facts["concurrent"]["batch_queries"] == len(queries) - 4
    assert facts["engine_flushes"] == 4
    assert facts["anytime"]["certified_recall_at_k"] <= facts["anytime"]["true_recall_at_k"]


def test_retrieval_check_rejects_a_wrong_answer(smoke, corpus):
    from repro.index import search

    store, _, queries, _, ref, scales = corpus
    res = search(queries[0], store, 5)
    wrong = ref[0].copy()
    wrong[res.ids[0]] += 0.5
    with pytest.raises(smoke.SmokeFailure, match="ids"):
        smoke.check_exact(res, wrong, scales[0], 5, store.dim, "q0")
    off = ref[0].copy()
    off[res.ids] += 1e-3
    with pytest.raises(smoke.SmokeFailure, match="fp_value_margin"):
        smoke.check_exact(res, off, scales[0], 5, store.dim, "q0")


def test_stage2a_programs_compile_for_resolved_backends(smoke, corpus):
    store, _, queries, _, _, _ = corpus
    progs = smoke.stage2a_programs(
        store, queries[0].shape[0],
        single_backend="batched_mirror", multi_backend="multiquery_mirror",
    )
    assert set(progs) == {"search", "search_batch"}
    assert all("tpu_custom_call" not in text for text in progs.values())


def test_pairwise_phase_matches_float64(smoke):
    facts = smoke.pairwise_phase(0, 600, 64)
    assert facts["exact_backend"] == "tiled"
    lo, hi = facts["prohd_interval"]
    assert lo <= facts["float64"] <= hi
    assert abs(facts["exact"] - facts["float64"]) <= facts["margin"]


def test_sharded_phase_agrees_on_one_device(smoke, corpus):
    store, _, queries, _, ref, scales = corpus
    facts = smoke.sharded_phase(store, queries[:3], ref, scales, k=5, shards=1)
    assert facts["slab_devices"] == [0] and facts["shards"] == 1


def _run_script(script: Path, cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, str(script)], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120,
    )


def test_script_fails_without_a_tpu():
    out = _run_script(ROOT / "chip_smoke.py", ROOT)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "no TPU" in out.stderr


def test_script_fails_alone_in_a_directory(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    out = _run_script(tmp_path / "chip_smoke.py", tmp_path)
    assert out.returncode != 0
    assert out.stdout == ""
