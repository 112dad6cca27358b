"""repro.obs — the unified tracing + metrics layer's own contract.

What's proved here (docs/api.md "Observability contract"):

* disabled by default: no events, no registry writes, inert spans;
* one ``search()`` yields ONE connected span tree under a single rid
  (index.search → cascade stages), schema-valid;
* one ``QueryEngine.search()`` yields ONE connected tree under a single
  rid across the async-batching + thread-pool-executor boundary
  (engine.search → engine.flush → index.search_batch → stages);
* metrics: typed get-or-create registry, log-bucket histograms,
  Prometheus text exposition, span auto-fold;
* JSONL export round-trips and validates;
* store snapshots, heartbeats, and fault chains all surface through the
  same layer.
"""
import json

import numpy as np
import pytest

from repro.index import SetStore, search
from repro.obs import (
    OBS_SCHEMA_VERSION,
    MetricsRegistry,
    SchemaError,
    exception_chain,
    export,
    metrics,
    report,
    trace,
    validate_events,
)
from strategies import query_near as _query
from strategies import ragged_corpus as _corpus

pytestmark = pytest.mark.obs

K = 4


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Every test starts and ends disabled with empty buffers."""
    trace.disable()
    trace.drain()
    yield
    trace.disable()
    trace.drain()


@pytest.fixture(scope="module")
def corpus():
    sets, rng = _corpus(3, n_sets=26, dup_every=3)
    q = _query(rng, sets, 4)
    store = SetStore(dim=4)
    store.add_many(sets)
    return store, q, sets


# ---------------------------------------------------------------------------
# disabled-by-default contract
# ---------------------------------------------------------------------------


class TestDisabled:
    def test_no_events_no_registry_writes(self, corpus):
        store, q, _ = corpus
        reg = metrics.registry()
        before = reg.names()
        assert not trace.enabled()
        search(q, store, K)
        assert trace.events() == []
        assert reg.names() == before

    def test_span_is_shared_inert_singleton(self):
        s1 = trace.span("a", k=1)
        s2 = trace.span("b")
        assert s1 is s2
        with s1 as s:
            s.set(x=1).event("inner", error=True)
        s1.finish()  # idempotent no-op
        assert trace.events() == []

    def test_event_and_record_stats_are_noops(self):
        trace.event("free", error=True, n=3)
        metrics.record_stats("x", {"a": 1.0})
        assert trace.events() == []
        assert "x.a" not in metrics.registry().names()


# ---------------------------------------------------------------------------
# acceptance: one search() = one connected single-rid tree
# ---------------------------------------------------------------------------


class TestSearchTree:
    def test_search_connected_single_rid_tree(self, corpus):
        store, q, _ = corpus
        with trace.capture() as get_events:
            res = search(q, store, K)
            events = get_events()
        summary = validate_events(events)
        assert len(summary["rids"]) == 1
        spans = {e["name"]: e for e in events if e["type"] == "span"}
        root = spans["index.search"]
        assert root["parent_id"] is None
        for stage in ("cascade.stage0", "cascade.stage2a", "cascade.stage2b"):
            assert spans[stage]["parent_id"] == root["span_id"]
            assert spans[stage]["rid"] == root["rid"]
        # stage spans closed before (and nested inside) the root
        assert root["dur_s"] >= spans["cascade.stage0"]["dur_s"]
        assert root["attrs"]["k"] == K
        assert root["attrs"]["degraded"] == res.degraded
        # backend resolution is a point event under the root's rid
        resolved = [e for e in events if e["name"] == "cascade.backend_resolved"]
        assert resolved and resolved[0]["rid"] == root["rid"]

    def test_search_stats_fold_into_registry(self, corpus):
        store, q, _ = corpus
        reg = metrics.registry()
        reg.reset()
        with trace.capture():
            search(q, store, K)
        names = reg.names()
        assert "span.index.search.s" in names
        assert "span.index.search.total" in names
        assert "index.search.exact_refines" in names
        assert reg.counter("span.index.search.total").value == 1.0

    def test_fault_surfaces_as_structured_chain_and_event(self, corpus):
        from repro.reliability import Fault, inject

        store, q, _ = corpus
        with trace.capture() as get_events:
            with inject(Fault("cascade.stage2a", action="raise")):
                res = search(q, store, K)
            events = get_events()
        assert res.degraded
        chain = res.stats["fault"]
        assert chain[0]["type"] == "InjectedFault"
        faults = [e for e in events if e["name"] == "cascade.fault"]
        assert len(faults) == 1 and faults[0]["error"]
        assert faults[0]["attrs"]["chain"][0]["type"] == "InjectedFault"
        validate_events(events)


# ---------------------------------------------------------------------------
# acceptance: one engine request = one connected single-rid tree across
# the async admission/flush machinery and the executor hop
# ---------------------------------------------------------------------------


class TestEngineTree:
    def test_engine_connected_single_rid_tree(self, corpus):
        import asyncio

        from repro.serve.engine import EngineConfig, QueryEngine
        from repro.serve.server import ProHDService, ServeConfig

        _store, q, sets = corpus
        svc = ProHDService(ServeConfig(min_store_bucket=8))
        for s in sets:
            svc.add_set(s)

        async def run():
            eng = QueryEngine(svc, EngineConfig(max_wait_s=0.0))
            try:
                return await eng.search(q, K)
            finally:
                await eng.close()

        with trace.capture() as get_events:
            res = asyncio.run(run())
            events = get_events()
        assert not res.degraded
        summary = validate_events(events)
        assert len(summary["rids"]) == 1
        spans = {e["name"]: e for e in events if e["type"] == "span"}
        root = spans["engine.search"]
        flush = spans["engine.flush"]
        batch = spans["index.search_batch"]
        assert root["parent_id"] is None
        assert flush["parent_id"] == root["span_id"]
        assert batch["parent_id"] == flush["span_id"]
        assert spans["cascade.stage0"]["parent_id"] == batch["span_id"]
        assert {root["rid"]} == {e["rid"] for e in events if e["type"] == "span"}
        admits = [e for e in events if e["name"] == "engine.admit"]
        assert admits and admits[0]["span_id"] == root["span_id"]
        # admission→completion metrics landed
        reg = metrics.registry()
        assert reg.histogram("engine.request_latency_s").count >= 1
        assert reg.counter("engine.flushes.total").value >= 1.0


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


class TestMetrics:
    def test_get_or_create_and_type_conflict(self):
        reg = MetricsRegistry()
        c = reg.counter("x.total")
        assert reg.counter("x.total") is c
        with pytest.raises(TypeError, match="x.total"):
            reg.gauge("x.total")

    def test_counter_rejects_negative(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError):
            reg.counter("c").inc(-1)

    def test_histogram_buckets_quantile(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat", unit="s")
        for v in (1e-4, 1e-3, 1e-3, 1e-2):
            h.observe(v)
        assert h.count == 4
        assert h.sum == pytest.approx(0.0121)
        assert h.mean == pytest.approx(0.0121 / 4)
        assert h.quantile(0.5) <= 1e-3 * 1.01
        snap = h.snapshot()
        assert snap["type"] == "histogram"
        assert sum(snap["buckets"].values()) == 4

    def test_prometheus_exposition(self):
        reg = MetricsRegistry()
        reg.counter("span.index.search.total").inc(3)
        reg.gauge("engine.queue_depth").set(2)
        reg.histogram("span.index.search.s", unit="s").observe(0.5)
        text = reg.to_prometheus()
        assert "# TYPE span_index_search_total counter" in text
        assert "span_index_search_total 3" in text
        assert "engine_queue_depth 2" in text
        assert 'span_index_search_s_bucket{le="+Inf"} 1' in text
        assert "span_index_search_s_count 1" in text

    def test_snapshot_shape(self):
        reg = MetricsRegistry()
        reg.counter("a").inc()
        reg.gauge("b").set(7)
        snap = reg.snapshot()
        assert snap["a"] == {"type": "counter", "unit": "", "value": 1.0}
        assert snap["b"]["value"] == 7.0


# ---------------------------------------------------------------------------
# export: JSONL round-trip + schema validation
# ---------------------------------------------------------------------------


class TestExport:
    def test_jsonl_roundtrip(self, corpus, tmp_path):
        store, q, _ = corpus
        path = tmp_path / "trace.jsonl"
        with trace.capture(jsonl=path) as get_events:
            search(q, store, K)
            in_memory = get_events()
        on_disk = export.read_jsonl(path)
        assert on_disk == in_memory
        assert validate_events(on_disk) == validate_events(in_memory)
        # every line is independently parseable (stream-appendable export)
        for line in path.read_text().splitlines():
            json.loads(line)

    def test_schema_version_exported(self):
        assert OBS_SCHEMA_VERSION == 1

    def test_validate_rejects_malformed(self):
        good = {
            "type": "span", "name": "x", "rid": "r1", "span_id": 1,
            "parent_id": None, "t_start": 0.0, "dur_s": 0.1,
            "status": "ok", "attrs": {},
        }
        validate_events([good])
        for corrupting in (
            lambda r: r.pop("rid"),
            lambda r: r.update(dur_s=-1.0),
            lambda r: r.update(status="maybe"),
            lambda r: r.update(parent_id=99),  # dangling parent
            lambda r: r.update(type="mystery"),
        ):
            bad = dict(good)
            corrupting(bad)
            with pytest.raises(SchemaError):
                validate_events([bad])

    def test_error_span_carries_chain(self):
        with trace.capture() as get_events:
            with pytest.raises(ValueError):
                with trace.span("boom"):
                    raise ValueError("inner")
            events = get_events()
        rec = events[0]
        assert rec["status"] == "error"
        assert rec["error"] == [{"type": "ValueError", "message": "inner"}]
        assert validate_events(events)["errors"] == 1


# ---------------------------------------------------------------------------
# exception chains
# ---------------------------------------------------------------------------


class TestExceptionChain:
    def test_cause_chain_preserved(self):
        try:
            try:
                raise KeyError("root")
            except KeyError as inner:
                raise RuntimeError("wrapper") from inner
        except RuntimeError as e:
            chain = exception_chain(e)
        assert [c["type"] for c in chain] == ["RuntimeError", "KeyError"]
        assert chain[1]["message"] == "'root'"

    def test_context_fallback_and_suppression(self):
        try:
            try:
                raise KeyError("ctx")
            except KeyError:
                raise RuntimeError("implicit")
        except RuntimeError as e:
            assert [c["type"] for c in exception_chain(e)] == [
                "RuntimeError", "KeyError",
            ]
        try:
            try:
                raise KeyError("hidden")
            except KeyError:
                raise RuntimeError("explicit") from None
        except RuntimeError as e:
            assert [c["type"] for c in exception_chain(e)] == ["RuntimeError"]


# ---------------------------------------------------------------------------
# store snapshot spans
# ---------------------------------------------------------------------------


class TestStoreSpans:
    def test_save_restore_spans(self, corpus, tmp_path):
        store, _q, _ = corpus
        with trace.capture() as get_events:
            snap = store.save(tmp_path)
            SetStore.restore(tmp_path)
            events = get_events()
        validate_events(events)
        spans = {e["name"]: e for e in events if e["type"] == "span"}
        save, rest = spans["store.save"], spans["store.restore"]
        total = sum(p.stat().st_size for p in snap.iterdir())
        assert save["attrs"]["bytes"] == rest["attrs"]["bytes"] == total
        assert save["attrs"]["n_sets"] == store.n_sets
        assert rest["attrs"]["dropped_buckets"] == 0
        assert rest["attrs"]["dropped_sets"] == 0

    def test_quarantine_counts_in_span(self, corpus, tmp_path):
        from repro.reliability import corrupt_snapshot

        store, _q, _ = corpus
        snap = store.save(tmp_path)
        corrupt_snapshot(snap, seed=0)
        with trace.capture() as get_events:
            restored = SetStore.restore(tmp_path, quarantine=True)
            events = get_events()
        rest = next(e for e in events if e["name"] == "store.restore")
        assert rest["attrs"]["quarantine"] is True
        assert rest["attrs"]["dropped_buckets"] == 1
        assert rest["attrs"]["dropped_sets"] == store.n_sets - restored.n_sets > 0

    def test_corruption_marks_span_error(self, corpus, tmp_path):
        from repro.reliability import StoreCorruption, corrupt_snapshot

        store, _q, _ = corpus
        snap = store.save(tmp_path)
        corrupt_snapshot(snap, seed=1)
        with trace.capture() as get_events:
            with pytest.raises(StoreCorruption):
                SetStore.restore(tmp_path)
            events = get_events()
        rest = next(e for e in events if e["name"] == "store.restore")
        assert rest["status"] == "error"
        assert rest["error"][0]["type"] == "StoreCorruption"


# ---------------------------------------------------------------------------
# heartbeat fold
# ---------------------------------------------------------------------------


class TestHeartbeat:
    def test_beats_fold_into_registry_when_enabled(self):
        from repro.train.fault_tolerance import Heartbeat

        reg = metrics.registry()
        reg.reset()
        hb = Heartbeat()
        hb.beat(wall_s=0.25)  # disabled: nothing lands
        assert "heartbeat.beats.total" not in reg.names()
        with trace.capture():
            hb.beat(wall_s=0.25)
            hb.beat()
        assert reg.counter("heartbeat.beats.total").value == 2.0
        h = reg.histogram("heartbeat.wall_s")
        assert h.count == 1 and h.sum == pytest.approx(0.25)
        assert hb.count == 3


# ---------------------------------------------------------------------------
# report rendering
# ---------------------------------------------------------------------------


class TestReport:
    def _capture(self, corpus):
        store, q, _ = corpus
        with trace.capture() as get_events:
            search(q, store, K)
            return get_events()

    def test_stage_table(self, corpus):
        events = self._capture(corpus)
        table = report.stage_table(events)
        assert "| index.search |" in table
        assert "| cascade.stage0 |" in table
        assert report.stage_table([]) == "(no spans captured)"

    def test_tree_nests_stages_under_root(self, corpus):
        events = self._capture(corpus)
        out = report.tree(events)
        lines = out.splitlines()
        assert lines[0].startswith("index.search")
        assert any(ln.startswith("  cascade.stage0") for ln in lines)

    def test_cli_renders_jsonl(self, corpus, tmp_path, capsys):
        store, q, _ = corpus
        path = tmp_path / "t.jsonl"
        with trace.capture(jsonl=path):
            search(q, store, K)
        assert report.main([str(path)]) == 0
        out = capsys.readouterr().out
        assert "index.search" in out and "1 rids" in out


# ---------------------------------------------------------------------------
# service payloads carry the certificate (PR 8 satellite regression)
# ---------------------------------------------------------------------------


class TestServicePayload:
    def test_search_payload_carries_certificate_and_degraded(self, corpus):
        from repro.serve.server import ProHDService, ServeConfig

        store, q, sets = corpus
        svc = ProHDService(ServeConfig(min_store_bucket=8))
        for s in sets:
            svc.add_set(s)
        rid = svc.submit_search(q, K)
        rid_any = svc.submit_search(q, K, mode="anytime", epsilon=0.25)
        results = svc.flush()
        out = results[rid]
        for key in ("ids", "values", "lower", "upper", "degraded",
                    "stage_reached", "stats", "certified_recall"):
            assert key in out, f"payload missing {key!r}"
        assert out["degraded"] is False
        # non-degraded exact: zero-width certified interval equal to the
        # values, full recall certificate
        assert out["lower"] == out["values"] == out["upper"]
        assert out["certified_recall"] == 1.0
        ref = search(q, store, K, method="exact")
        assert out["ids"] == ref.ids.tolist()
        np.testing.assert_allclose(out["values"], ref.values)
        # the anytime payload carries the SAME certificate surface: per-hit
        # intervals bracketing the values plus the recall certificate
        out_any = results[rid_any]
        for key in ("ids", "values", "lower", "upper", "degraded",
                    "stage_reached", "stats", "certified_recall"):
            assert key in out_any, f"anytime payload missing {key!r}"
        assert out_any["stats"]["mode"] == "anytime"
        assert 0.0 <= out_any["certified_recall"] <= 1.0
        for lo, v, up in zip(out_any["lower"], out_any["values"], out_any["upper"]):
            assert lo <= v + 1e-6 and v - 1e-6 <= up


# ---------------------------------------------------------------------------
# the front door's spans: hd.set_distance -> hd.validate, hd.dispatch
# ---------------------------------------------------------------------------


def _clouds(n=256, d=16, seed=0):
    import jax

    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    a = jax.random.uniform(ka, (n, d))
    b = jax.random.uniform(kb, (n, d)) + 0.1
    return a, b


def _prohd_call(a, b):
    from repro.hd import HDConfig, set_distance

    return set_distance(a, b, method="prohd", config=HDConfig(alpha=0.05))


class TestFrontDoorSpans:
    def test_one_call_one_tree_under_the_ambient_span(self):
        a, b = _clouds()
        with trace.capture() as get_events:
            with trace.span("serve.flush") as outer:
                res = _prohd_call(a, b)
            events = get_events()
        validate_events(events)
        spans = {e["name"]: e for e in events if e["type"] == "span"}
        root = spans["hd.set_distance"]
        assert root["parent_id"] == outer.span_id
        for child in ("hd.validate", "hd.dispatch"):
            assert spans[child]["parent_id"] == root["span_id"]
        assert {e["rid"] for e in events} == {outer.rid}
        assert root["attrs"] == {"variant": "hausdorff", "method": "prohd",
                                 "backend": res.meta.backend, "n_a": 256, "n_b": 256,
                                 "d": 16}
        assert spans["hd.validate"]["attrs"]["bytes"] == a.nbytes + b.nbytes
        # the validation runs inside the call, before the dispatch
        v, dsp = spans["hd.validate"], spans["hd.dispatch"]
        assert root["t_start"] <= v["t_start"] <= dsp["t_start"]
        assert v["t_start"] + v["dur_s"] <= root["t_start"] + root["dur_s"] + 1e-3

    def test_off_no_events_same_result(self):
        a, b = _clouds(seed=1)
        off = _prohd_call(a, b)
        assert trace.events() == []
        with trace.capture():
            on = _prohd_call(a, b)
        for x, y in ((off.value, on.value), (off.lower, on.lower), (off.upper, on.upper)):
            assert np.asarray(x).tobytes() == np.asarray(y).tobytes()

    def test_no_span_under_jit(self):
        import jax

        from repro.hd import HDConfig, HDEngine

        a, b = _clouds(seed=2)
        engine = HDEngine(method="prohd", config=HDConfig(alpha=0.05))
        with trace.capture() as get_events:
            jax.block_until_ready(jax.jit(lambda x, y: engine(x, y).value)(a, b))
            events = get_events()
        assert not [e for e in events if e["name"].startswith("hd.")]

    def test_validate_false_opens_no_validate_span(self):
        from repro.hd import set_distance

        a, b = _clouds(seed=3)
        with trace.capture() as get_events:
            set_distance(a, b, validate=False)
            names = [e["name"] for e in get_events()]
        assert names == ["hd.dispatch", "hd.set_distance"]


class TestCaptureRestores:
    @pytest.mark.parametrize("xla", [False, True])
    def test_prior_settings_come_back(self, tmp_path, xla):
        outer = tmp_path / "outer.jsonl"
        trace.enable(jsonl=outer, xla=xla)
        with trace.span("before"):
            pass
        with trace.capture() as get_events:
            with trace.span("inside"):
                pass
            assert [e["name"] for e in get_events()] == ["inside"]
        assert trace.enabled() and trace._STATE.xla is xla
        with trace.span("after"):
            pass
        trace.disable()
        assert [r["name"] for r in export.read_jsonl(outer)] == ["before", "after"]

    def test_off_stays_off(self):
        with trace.capture():
            pass
        assert not trace.enabled() and not trace._STATE.xla and trace._STATE.jsonl is None


# ---------------------------------------------------------------------------
# obs.xla: a profile's device ops resolved to ProHD's named scopes
# ---------------------------------------------------------------------------


def _profile_ops(tmp_path, fn) -> dict[str, str]:
    """``{op name: hlo_module}`` of the ops a CPU profile of ``fn`` records."""
    import glob

    import jax
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        fn()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    ops = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if "hlo_op" in stats and e.duration_ns > 0:
                    ops[e.name.split(" = ")[0]] = stats.get("hlo_module", "")
    return ops


class TestXlaScopes:
    @pytest.fixture(autouse=True)
    def _fresh_notes(self):
        from repro.obs import xla

        xla.clear()
        yield
        xla.clear()

    def test_profiled_ops_map_to_the_four_scopes(self, tmp_path):
        import jax

        from repro.core.prohd import SCOPES
        from repro.obs import xla

        a, b = _clouds(seed=4)
        jax.block_until_ready(_prohd_call(a, b).value)
        with trace.capture():
            ops = _profile_ops(tmp_path, lambda: jax.block_until_ready(_prohd_call(a, b).value))
        assert len(xla._NOTES) == 1
        scopes = xla.op_scopes()
        mine = [xla.bare(name) for name, module in ops.items() if module == "jit_prohd"]
        assert {scopes.get(n) for n in mine} >= set(SCOPES)
        # an op left out carries no op_name: the compiler made it
        (text,) = xla._TEXTS.values()
        named = xla.scopes_from_hlo(text, ("",))
        assert [n for n in mine if n not in scopes and n in named] == []

    def test_searchsorted_loop_body_is_the_lower_bound(self):
        import re

        from repro.core.prohd import ProHDConfig, prohd
        from repro.obs import xla

        a, b = _clouds(seed=5)
        text = prohd.lower(a, b, ProHDConfig(alpha=0.05)).compile().as_text()
        scopes = xla.scopes_from_hlo(text, ("prohd.",))
        bodies = re.findall(r"while\(.*?body=%?([\w.\-]+).*searchsorted", text)
        assert bodies
        for body in set(bodies):
            block = re.search(r"^%?" + re.escape(body) + r" .*?\{\n(.*?)\n\}", text,
                              re.M | re.S).group(1)
            named = re.findall(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*\bop_name=", block, re.M)
            assert named and {scopes.get(n) for n in named} == {"prohd.lower_bound"}

    def test_note_lowers_nothing_in_the_window(self):
        import jax
        import jax.monitoring

        from repro.obs import xla

        a, b = _clouds(seed=6)
        jax.block_until_ready(_prohd_call(a, b).value)  # warm
        seen = {"on": False, "compiles": 0, "traces": 0}

        def listen(name, _secs, **_kw):
            if seen["on"]:
                seen["compiles"] += name == "/jax/core/compile/backend_compile_duration"
                seen["traces"] += name == "/jax/core/compile/jaxpr_trace_duration"

        jax.monitoring.register_event_duration_secs_listener(listen)
        seen["on"] = True
        with trace.capture(xla=True):
            jax.block_until_ready(_prohd_call(a, b).value)
        seen["on"] = False
        assert (seen["compiles"], seen["traces"]) == (0, 0)
        assert len(xla._NOTES) == 1
        assert set(xla.op_scopes().values()) >= {"prohd.lower_bound", "prohd.select"}

    def test_note_skips_tracers_and_dedups(self):
        import jax

        from repro.core.prohd import ProHDConfig, prohd
        from repro.obs import xla

        a, b = _clouds(seed=7)
        cfg = ProHDConfig(alpha=0.05)
        jax.jit(lambda x, y: xla.note(prohd, x, y, cfg) or x.sum())(a, b)
        assert xla._NOTES == {}
        xla.note(prohd, a, b, cfg, key=None)
        xla.note(prohd, a + 1, b, cfg, key=None)
        assert len(xla._NOTES) == 1

    def test_scopes_only_change_metadata(self, monkeypatch):
        """The scoped program is the unscoped one but for its metadata."""
        import contextlib
        import re

        import jax

        from repro.core.prohd import ProHDConfig, prohd

        a, b = _clouds(seed=8)
        cfg = ProHDConfig(alpha=0.05)

        def program():
            text = prohd.lower(a, b, cfg).compile().as_text()
            keep = [ln for ln in text.splitlines() if " = " in ln or ln.endswith("{")]
            return text, [re.sub(r", (metadata=\{[^}]*\}|stack_frame_id=\d+)", "", ln)
                          for ln in keep]

        scoped_text, scoped = program()
        jax.clear_caches()
        monkeypatch.setattr(jax, "named_scope", lambda _name: contextlib.nullcontext())
        plain_text, plain = program()
        jax.clear_caches()
        assert "prohd.lower_bound" in scoped_text and "prohd.lower_bound" not in plain_text
        assert scoped == plain
