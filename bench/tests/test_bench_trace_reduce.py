"""The trace reduction, on hand-made intervals and on a small trace
recorded on the CPU."""
from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import trace_reduce as tr  # noqa: E402

MS = 1e6  # ns


def test_busy_is_the_union_of_device_intervals_in_the_window():
    ts = tr.TraceSummary(
        window=(0.0, 10 * MS),
        ops={0: [("a", 1 * MS, 3 * MS), ("b", 1.5 * MS, 2.5 * MS), ("c", 3 * MS, 3.8 * MS),
                 ("d", 4.5 * MS, 5 * MS), ("a", 9 * MS, 12 * MS)]},
        spans=[("engine.flush", 0.0, 5 * MS), ("cascade.stage2b", 3.5 * MS, 5 * MS),
               ("engine.search", 0.0, 10 * MS)])
    assert ts.window_s == pytest.approx(0.010)
    assert ts.busy_s == pytest.approx(0.0043)          # [1,3.8], [4.5,5], [9,10]
    # b runs inside a: a's own time leaves it out
    assert ts.op_seconds(lambda n: n == "a") == pytest.approx(0.002)
    assert ts.top_ops() == [["a", pytest.approx(0.002)], ["b", pytest.approx(0.001)],
                            ["c", pytest.approx(0.0008)], ["d", pytest.approx(0.0005)]]
    gaps = dict(ts.idle_by_span())
    # [0,1] under engine.flush, [3.8,4.5] under the shorter cascade.stage2b,
    # [5,9] only under engine.search
    assert gaps == {"engine.flush": pytest.approx(0.001),
                    "cascade.stage2b": pytest.approx(0.0007),
                    "engine.search": pytest.approx(0.004)}


def test_op_names_drop_the_hlo_text():
    assert tr.op_name("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop") == "%fusion.12"
    assert tr.op_name("copy.3") == "copy.3"


def test_busy_averages_over_devices():
    ts = tr.TraceSummary(window=(0.0, 4 * MS),
                         ops={0: [("x", 0.0, 4 * MS)], 1: [("x", 0.0, 2 * MS)]}, spans=[])
    assert ts.busy_s == pytest.approx(0.003)


def test_a_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x.T).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        for i in range(3):
            with jax.profiler.TraceAnnotation("engine.flush"):
                f(x).block_until_ready()
            time.sleep(0.01)
    jax.profiler.stop_trace()
    ts = tr.reduce(tr.find_xplane(str(tmp_path)))
    assert ts.window_s >= 0.03
    assert 0 < ts.busy_s < ts.window_s
    assert ts.top_ops() and all(secs > 0 for _, secs in ts.top_ops())
    assert any(name == "engine.flush" for name, _, _ in ts.spans)
    b = ts.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert sum(s for _, s in b["idle_gaps"]) == pytest.approx(ts.window_s - ts.busy_s, rel=1e-6)
