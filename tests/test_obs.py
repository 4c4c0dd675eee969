"""Observability layer: tracer no-op guarantee, span round-trip,
metrics exposition, PlanCache quarantine schema, explain reports, and
the tracing-never-perturbs-results bit-identity contract."""
import json
import re

import numpy as np
import pytest

from repro.obs.metrics import MetricsRegistry, default_registry
from repro.obs.trace import (
    NULL_SPAN,
    Tracer,
    get_tracer,
    set_tracer,
    use_tracer,
)


def fake_clock(start=100.0, step=0.5):
    t = [start - step]

    def clock():
        t[0] += step
        return t[0]

    return clock


# ------------------------------------------------------------- tracer ---
class TestTracer:
    def test_disabled_tracer_is_noop(self):
        tr = Tracer(enabled=False)
        sp = tr.span("a", x=1)
        assert sp is NULL_SPAN          # one shared object, no alloc
        assert tr.span("b") is NULL_SPAN
        with sp as s:
            s.set(y=2).event("e")
        tr.event("orphan")
        assert tr.roots == []
        assert tr.to_dict() == []
        assert tr.to_chrome_trace()["traceEvents"] == []

    def test_default_process_tracer_disabled(self):
        assert get_tracer().enabled is False

    def test_nesting_and_attrs_round_trip_chrome(self):
        tr = Tracer(clock=fake_clock(step=1.0))
        with tr.span("outer", op="spmm", n=32) as outer:
            outer.event("mark", phase="mid")
            with tr.span("inner", strategy="fast"):
                pass
        doc = json.loads(json.dumps(tr.to_chrome_trace()))
        evs = doc["traceEvents"]
        by_name = {e["name"]: e for e in evs}
        out, inn, mark = (by_name["outer"], by_name["inner"],
                          by_name["mark"])
        assert out["ph"] == "X" and inn["ph"] == "X"
        assert mark["ph"] == "i" and mark["s"] == "t"
        assert out["args"] == {"op": "spmm", "n": 32}
        assert inn["args"] == {"strategy": "fast"}
        assert mark["args"] == {"phase": "mid"}
        # containment: inner lives within [outer.ts, outer.ts+dur]
        assert out["ts"] <= inn["ts"]
        assert inn["ts"] + inn["dur"] <= out["ts"] + out["dur"]
        assert out["ts"] <= mark["ts"] <= out["ts"] + out["dur"]

    def test_dict_tree_structure(self):
        tr = Tracer(clock=fake_clock(step=1.0))
        with tr.span("root"):
            with tr.span("child", k=1):
                pass
            with tr.span("child", k=2):
                pass
        (tree,) = tr.to_dict()
        assert tree["name"] == "root"
        assert [c["attrs"]["k"] for c in tree["children"]] == [1, 2]
        assert tree["start_s"] == 0.0
        assert tree["dur_s"] == pytest.approx(5.0)

    def test_set_after_open_and_late_attrs(self):
        tr = Tracer(clock=fake_clock())
        with tr.span("s", a=1) as sp:
            sp.set(rid=7)
        assert tr.roots[0].attrs == {"a": 1, "rid": 7}

    def test_use_tracer_scopes_and_restores(self):
        prev = get_tracer()
        t = Tracer()
        with use_tracer(t):
            assert get_tracer() is t
            with get_tracer().span("x"):
                pass
        assert get_tracer() is prev
        assert [s.name for s in t.roots] == ["x"]

    def test_out_of_order_close_tolerated(self):
        tr = Tracer(clock=fake_clock())
        a = tr.span("a").open()
        tr.span("b").open()          # never closed explicitly
        a.close()                    # pops b too
        assert tr.current is None

    def test_event_outside_span_dropped(self):
        tr = Tracer(clock=fake_clock())
        tr.event("orphan")
        assert tr.roots == []


# ------------------------------------------------------------ metrics ---
# One Prometheus exposition line: name{labels} value  (labels optional).
_EXPO_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\""
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*\})?"
    r" (\+Inf|-?[0-9.e+-]+)$")


class TestMetrics:
    def test_counter_gauge_histogram_exposition_parses(self):
        m = MetricsRegistry()
        m.counter("requests_total", "Total requests").inc(3)
        m.counter("errors_total", "Errors", labels=("kind",)).inc(
            kind="nan")
        m.gauge("depth", "Queue depth").set(7)
        h = m.histogram("lat_s", "Latency", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(5.0)
        text = m.exposition()
        for line in text.strip().splitlines():
            if line.startswith("#"):
                assert re.match(r"^# (HELP|TYPE) ", line), line
            else:
                assert _EXPO_LINE.match(line), line
        assert "requests_total 3" in text
        assert 'errors_total{kind="nan"} 1' in text
        # cumulative buckets + sum/count
        assert 'lat_s_bucket{le="0.1"} 1' in text
        assert 'lat_s_bucket{le="1"} 2' in text
        assert 'lat_s_bucket{le="+Inf"} 3' in text
        assert "lat_s_count 3" in text

    def test_counter_int_view_and_series(self):
        m = MetricsRegistry()
        c = m.counter("n_total")
        c.inc()
        c.inc(2)
        assert c.value == 3 and isinstance(c.value, int)
        lab = m.counter("by_total", labels=("reason",))
        lab.inc(reason="a")
        lab.inc(reason="a")
        lab.inc(reason="b")
        assert lab.series() == {"a": 2, "b": 1}
        assert lab.get(reason="a") == 2

    def test_counter_rejects_negative_and_label_mismatch(self):
        m = MetricsRegistry()
        c = m.counter("c_total", labels=("k",))
        with pytest.raises(ValueError):
            c.inc(-1, k="x")
        with pytest.raises(ValueError):
            c.inc(other="x")
        with pytest.raises(ValueError):
            m.counter("c_total", labels=("different",))
        with pytest.raises(ValueError):
            m.gauge("c_total")       # kind clash

    def test_get_or_create_returns_same_instrument(self):
        m = MetricsRegistry()
        assert m.counter("x_total") is m.counter("x_total")
        assert "x_total" in m
        assert m["x_total"].kind == "counter"

    def test_snapshot_json_roundtrip(self):
        m = MetricsRegistry()
        m.counter("a_total", "help a").inc()
        m.histogram("h_s", buckets=(1.0,)).observe(0.5)
        snap = json.loads(json.dumps(m.snapshot()))
        assert snap["a_total"] == {"type": "counter", "help": "help a",
                                   "value": 1}
        hs = snap["h_s"]["series"][0]
        assert hs["count"] == 1 and hs["sum"] == 0.5
        assert hs["buckets"]["1"] == 1


# -------------------------------------------------- PlanCache metrics ---
class TestPlanCacheMetrics:
    def test_quarantine_schema_and_bytes(self, tmp_path):
        from repro.tune.cache import CACHE_VERSION, PlanCache

        pc = PlanCache(root=str(tmp_path))
        (tmp_path / "bad1.json").write_text("{not json")
        (tmp_path / "bad2.json").write_text(
            '{"version": %d, "config": {}, "checksum": "nope"}'
            % CACHE_VERSION)
        assert pc.get("bad1") is None
        assert pc.get("bad2") is None
        st = pc.stats()
        assert st["quarantined"] == 2
        assert st["quarantined_by_reason"] == {
            "unparseable": 1, "checksum_mismatch": 1}
        assert st["quarantined_bytes"] > 0
        assert st["quarantine_dir_files"] == 2
        assert st["misses"] == 2 and st["hits"] == 0
        text = pc.metrics.exposition()
        assert ('tune_cache_quarantined_total{reason="unparseable"} 1'
                in text)
        assert "tune_cache_quarantined_bytes_total" in text

    def test_hit_miss_counters(self, tmp_path):
        from repro.tune.cache import PlanCache
        from repro.tune.model import TuneConfig

        pc = PlanCache(root=str(tmp_path))
        assert pc.get("k") is None          # cold miss
        pc.put("k", TuneConfig(threshold=3))
        assert pc.get("k") is not None
        st = pc.stats()
        assert st["hits"] == 1 and st["misses"] == 1


# ------------------------------------------------------- bit identity ---
class TestBitIdentity:
    def test_traced_apply_is_bit_identical(self):
        import jax.numpy as jnp

        from repro.core.spmm import LibraSpMM
        from repro.sparse.generate import power_law_csr

        a = power_law_csr(128, 96, avg_row=6.0, seed=3)
        rng = np.random.default_rng(0)
        b = jnp.asarray(rng.standard_normal((96, 16)).astype(np.float32))
        base = np.asarray(LibraSpMM(a)(b))
        with use_tracer(Tracer()) as tr:
            traced = np.asarray(LibraSpMM(a)(b))
        assert np.array_equal(base, traced)
        names = {s.name for s in tr.roots}
        assert "preprocess.spmm" in names
        assert any(s.name == "kernels.compile"
                   for s in tr.roots)

    def test_traced_engine_mix_is_bit_identical(self):
        from repro.serve import GraphRegistry, SparseEngine
        from repro.sparse.generate import power_law_csr

        a = power_law_csr(64, 48, avg_row=5.0, seed=1)
        rng = np.random.default_rng(2)
        bs = [rng.standard_normal((48, 16)).astype(np.float32)
              for _ in range(4)]

        def serve(tracer):
            reg = GraphRegistry(width_buckets=(16,), panel_buckets=(1, 4))
            reg.register(a, name="g", ops=("spmm",))
            eng = SparseEngine(reg, tracer=tracer)
            rids = [eng.submit("g", "spmm", b=b) for b in bs]
            out = eng.flush()
            return [np.asarray(out[r]) for r in rids]

        plain = serve(None)
        tr = Tracer()
        traced = serve(tr)
        assert all(np.array_equal(p, t) for p, t in zip(plain, traced))
        assert tr.roots       # something was actually recorded


# ----------------------------------------------- engine lifecycle trace ---
class TestEngineLifecycle:
    def test_admit_to_complete_trace(self):
        from repro.serve import GraphRegistry, SparseEngine
        from repro.sparse.generate import power_law_csr

        a = power_law_csr(64, 48, avg_row=5.0, seed=1)
        reg = GraphRegistry(width_buckets=(16,), panel_buckets=(1, 4))
        reg.register(a, name="g", ops=("spmm",))
        tr = Tracer()
        eng = SparseEngine(reg, tracer=tr)
        rng = np.random.default_rng(0)
        rids = [eng.submit(
            "g", "spmm",
            b=rng.standard_normal((48, 16)).astype(np.float32))
            for _ in range(3)]
        eng.flush()
        doc = json.loads(json.dumps(tr.to_chrome_trace()))
        evs = doc["traceEvents"]
        admits = [e for e in evs if e["name"] == "serve.admit"]
        completes = [e for e in evs if e["name"] == "serve.complete"]
        assert sorted(e["args"]["rid"] for e in admits) == sorted(rids)
        assert sorted(e["args"]["rid"] for e in completes) == sorted(rids)
        assert all(e["args"]["ok"] for e in completes)
        names = {e["name"] for e in evs}
        assert {"serve.flush", "serve.bucket", "serve.execute",
                "serve.apply"} <= names
        # every complete event happens inside the flush span
        fl = next(e for e in evs if e["name"] == "serve.flush")
        for e in completes:
            assert fl["ts"] <= e["ts"] <= fl["ts"] + fl["dur"]

    def test_engine_metrics_exposition(self):
        from repro.serve import GraphRegistry, SparseEngine
        from repro.sparse.generate import power_law_csr

        a = power_law_csr(64, 48, avg_row=5.0, seed=1)
        reg = GraphRegistry(width_buckets=(16,), panel_buckets=(1, 4))
        reg.register(a, name="g", ops=("spmm",))
        eng = SparseEngine(reg)
        rng = np.random.default_rng(0)
        eng.submit("g", "spmm",
                   b=rng.standard_normal((48, 16)).astype(np.float32))
        eng.flush()
        text = eng.metrics.exposition()
        assert "serve_submitted_total 1" in text
        assert "serve_served_total 1" in text
        assert 'serve_applies_total{strategy="fast"} 1' in text
        st = eng.stats()
        assert st["submitted"] == 1 and isinstance(st["submitted"], int)
        assert reg.stats()["registered_total"] == 1
        assert "registry_registered_total 1" in reg.metrics.exposition()

    def test_partition_gauges_published(self):
        from repro.dist.partition import partition_spmm
        from repro.sparse.generate import power_law_csr

        a = power_law_csr(128, 96, avg_row=6.0, seed=3)
        partition_spmm(a, 2, tune="off")
        m = default_registry()
        assert m["dist_shards"].get(op="spmm") == 2
        assert m["dist_nnz_max_over_mean"].get(op="spmm") >= 1.0


# ------------------------------------------------------------ explain ---
class TestExplain:
    def _corpus(self):
        from repro.sparse.generate import suitesparse_like_corpus

        return suitesparse_like_corpus(n_small=4, seed=7)

    REQUIRED = ("kind", "shape", "tc_fraction", "density_hist",
                "segments", "padding", "occupancy")

    def test_reports_all_quantities_for_corpus(self):
        from repro.obs.explain import explain_spmm, render_table

        for name, a in self._corpus().items():
            rep = explain_spmm(a)
            for key in self.REQUIRED:
                assert key in rep, (name, key)
            assert 0.0 <= rep["tc_fraction"] <= 1.0
            assert len(rep["density_hist"]["vector_occupancy"]) == 8
            assert rep["occupancy"]["pipeline_depth"] >= 1
            assert 0.0 <= rep["padding"]["total_pad_frac"] <= 1.0
            table = render_table(rep, title=name)
            assert "tc_fraction" in table and name in table

    def test_measured_side(self):
        from repro.obs.explain import explain_spmm

        name, a = next(iter(self._corpus().items()))
        rep = explain_spmm(a, measure=True, width=16, reps=1)
        assert rep["measured"]["wall_s"] > 0
        # interpret-mode executables expose HLO text → flops/bytes
        assert rep["measured"].get("hlo_flops", 0) >= 0

    def test_sddmm_and_plan_paths(self):
        from repro.core.sddmm import LibraSDDMM
        from repro.obs.explain import explain_plan, explain_sddmm

        name, a = next(iter(self._corpus().items()))
        op = LibraSDDMM(a)
        rep = explain_sddmm(op, a=a)
        assert rep["kind"] == "sddmm"
        rep2 = explain_plan(op.plan, cfg=op.tune_config)
        assert rep2["kind"] == "sddmm"
        assert rep2["density_hist"]["source"] == "tc_bitmap"

    def test_explain_partition(self):
        from repro.dist.partition import partition_spmm
        from repro.obs.explain import explain_partition, render_table
        from repro.sparse.generate import power_law_csr

        a = power_law_csr(128, 96, avg_row=6.0, seed=3)
        part = partition_spmm(a, 2, tune="off")
        rep = explain_partition(part)
        assert rep["n_shards"] == 2
        assert sum(rep["shard_nnz"]) == a.nnz
        assert rep["halo_waste_frac"] >= 0.0
        assert "halo_waste_frac" in render_table(rep)

    def test_explain_registry_entry(self):
        from repro.obs.explain import explain_entry
        from repro.serve import GraphRegistry
        from repro.sparse.generate import power_law_csr

        a = power_law_csr(64, 48, avg_row=5.0, seed=1)
        reg = GraphRegistry(width_buckets=(16,), panel_buckets=(1, 4))
        reg.register(a, name="g", ops=("spmm",))
        rep = explain_entry(reg, "g", "spmm")
        assert rep["kind"] == "spmm"
        assert rep["registry"]["name"] == "g"


# ------------------------------------------------------- trace overhead ---
def test_disabled_span_overhead_is_small():
    """The disabled path must stay within the same order of magnitude as
    a bare function call (guards accidental allocation on the hot path);
    the enabled-path tax is gated by the serve/obs_overhead bench row."""
    import timeit as _t

    tr = Tracer(enabled=False)

    def instrumented():
        with tr.span("x", a=1):
            pass

    def bare():
        pass

    t_ins = min(_t.repeat(instrumented, number=20000, repeat=3))
    t_bare = min(_t.repeat(bare, number=20000, repeat=3))
    assert t_ins < t_bare * 50 + 0.05   # generous CI headroom


# ---------------------------------------------------- histogram timing ---
class TestHistogramTime:
    def test_time_observes_elapsed(self):
        m = MetricsRegistry()
        h = m.histogram("op_s", buckets=(1e9,))
        with h.time() as timing:
            pass
        assert timing.elapsed >= 0.0
        (series,) = m.snapshot()["op_s"]["series"]
        assert series["count"] == 1
        assert series["sum"] == pytest.approx(timing.elapsed)

    def test_time_with_labels(self):
        m = MetricsRegistry()
        h = m.histogram("op_s", labels=("kind",), buckets=(1e9,))
        with h.time(kind="flush"):
            pass
        snap = m.snapshot()["op_s"]["series"]
        assert [s["labels"] for s in snap] == [{"kind": "flush"}]

    def test_time_validates_labels_eagerly(self):
        m = MetricsRegistry()
        h = m.histogram("op_s", labels=("kind",))
        with pytest.raises(ValueError):
            h.time(wrong="x")           # before the block runs

    def test_time_records_on_exception(self):
        m = MetricsRegistry()
        h = m.histogram("op_s", buckets=(1e9,))
        with pytest.raises(RuntimeError):
            with h.time():
                raise RuntimeError("boom")
        (series,) = m.snapshot()["op_s"]["series"]
        assert series["count"] == 1

    def test_engine_flush_uses_histogram(self):
        from repro.serve import GraphRegistry, SparseEngine
        from repro.sparse.generate import power_law_csr

        a = power_law_csr(64, 48, avg_row=5.0, seed=1)
        reg = GraphRegistry(width_buckets=(16,), panel_buckets=(1, 4))
        reg.register(a, name="g", ops=("spmm",))
        eng = SparseEngine(reg)
        rng = np.random.default_rng(0)
        eng.submit("g", "spmm",
                   b=rng.standard_normal((48, 16)).astype(np.float32))
        eng.flush()
        snap = eng.metrics.snapshot()["serve_flush_seconds"]["series"]
        assert snap[0]["count"] == 1 and snap[0]["sum"] > 0
        # stats()' requests_per_s view still fed from the same wall
        assert eng.stats()["requests_per_s"] > 0


# ---------------------------------------------- null metrics registry ---
class TestNullMetricsRegistry:
    def test_discards_writes_but_keeps_api(self):
        from repro.obs.metrics import NullMetricsRegistry

        m = NullMetricsRegistry()
        c = m.counter("a_total", "help")
        c.inc(5)
        assert c.value == 0
        g = m.gauge("g")
        g.set(3)
        g.inc()
        assert g.get() == 0
        h = m.histogram("h_s", buckets=(1.0,))
        h.observe(0.5)
        with h.time() as timing:
            pass
        assert timing.elapsed >= 0.0     # timer still measures
        assert m.snapshot()["h_s"]["series"] == []   # ...nothing lands

    def test_engine_runs_on_null_registry(self):
        from repro.obs.metrics import NullMetricsRegistry
        from repro.serve import GraphRegistry, SparseEngine
        from repro.sparse.generate import power_law_csr

        a = power_law_csr(64, 48, avg_row=5.0, seed=1)
        reg = GraphRegistry(width_buckets=(16,), panel_buckets=(1, 4))
        reg.register(a, name="g", ops=("spmm",))
        eng = SparseEngine(reg, metrics=NullMetricsRegistry())
        rng = np.random.default_rng(0)
        rid = eng.submit(
            "g", "spmm",
            b=rng.standard_normal((48, 16)).astype(np.float32))
        out = eng.flush()
        assert rid in out
        assert "serve_submitted_total 0" in eng.metrics.exposition()


# --------------------------------------------------------- flow events ---
class TestFlowEvents:
    def test_request_lifecycle_linked_by_flow(self):
        from repro.serve import GraphRegistry, SparseEngine
        from repro.sparse.generate import power_law_csr

        a = power_law_csr(64, 48, avg_row=5.0, seed=1)
        reg = GraphRegistry(width_buckets=(16,), panel_buckets=(1, 4))
        reg.register(a, name="g", ops=("spmm",))
        tr = Tracer()
        eng = SparseEngine(reg, tracer=tr)
        rng = np.random.default_rng(0)
        rids = [eng.submit(
            "g", "spmm",
            b=rng.standard_normal((48, 16)).astype(np.float32))
            for _ in range(2)]
        eng.flush()
        doc = json.loads(json.dumps(tr.to_chrome_trace()))
        evs = doc["traceEvents"]
        for rid in rids:
            chain = [e for e in evs if e.get("cat") == "repro.flow"
                     and e["name"] == f"rid{rid}"]
            chain.sort(key=lambda e: e["ts"])
            # admit → execute → complete: start, step, finish
            assert [e["ph"] for e in chain] == ["s", "t", "f"]
            assert chain[-1]["bp"] == "e"
            assert len({e["id"] for e in chain}) == 1
        # distinct rids get distinct flow ids
        ids = {e["id"] for e in evs if e.get("cat") == "repro.flow"}
        assert len(ids) == len(rids)
        # reserved flow attrs never leak into exported args
        for e in evs:
            args = e.get("args", {})
            assert "flow_id" not in args and "flow_ids" not in args

    def test_spans_without_flow_attrs_emit_no_flow_events(self):
        tr = Tracer(clock=fake_clock())
        with tr.span("a"):
            pass
        evs = tr.to_chrome_trace()["traceEvents"]
        assert all(e.get("cat") != "repro.flow" for e in evs)

    def test_single_point_flow_dropped(self):
        tr = Tracer(clock=fake_clock())
        with tr.span("a", flow_id="only-once"):
            pass
        evs = tr.to_chrome_trace()["traceEvents"]
        # a flow needs ≥2 points to mean anything; singletons vanish
        assert all(e.get("cat") != "repro.flow" for e in evs)


# ---------------------------------------------- plan build and profiler ---
class TestPlanBuildSpans:
    @pytest.fixture(scope="class")
    def built(self):
        from repro.api import ExecSpec
        from repro.models.gnn import GraphOps
        from repro.sparse.generate import power_law_csr

        a = power_law_csr(96, 96, avg_row=6.0, seed=5)
        tr = Tracer()
        with use_tracer(tr):
            ops = GraphOps(a, spec=ExecSpec(tune="model"))
        return tr, ops

    def test_graphops_build_tree_has_three_legs(self, built):
        tr, _ = built
        (root,) = tr.roots
        assert root.name == "graphops.build"
        kids = root.children
        assert [c.name for c in kids] == [
            "graphops.transpose", "graphops.features", "graphops.leg",
            "graphops.leg", "graphops.leg", "graphops.edges"]
        legs = [c for c in kids if c.name == "graphops.leg"]
        assert [c.attrs["leg"] for c in legs] == ["spmm", "spmm_t", "sddmm"]
        for leg, pre in zip(legs, ("preprocess.spmm", "preprocess.spmm",
                                   "preprocess.sddmm")):
            names = [c.name for c in leg.children]
            assert names == ["tune.model", pre], names
        # The children tile the root in order; what is left is its self time.
        assert all(a.t1 <= b.t0 for a, b in zip(kids, kids[1:]))
        assert root.t0 <= kids[0].t0 and kids[-1].t1 <= root.t1
        assert sum(c.duration for c in kids) <= root.duration

    @pytest.mark.parametrize("leg", ["spmm", "spmm_t", "sddmm"])
    def test_preprocess_counters_match_explain(self, built, leg):
        from repro.obs.explain import explain_plan, plan_counts

        tr, ops = built
        arrs = {"spmm": ops.arrs, "spmm_t": ops.arrs_t,
                "sddmm": ops.arrs_sd}[leg]
        kind = "sddmm" if leg == "sddmm" else "spmm"
        (span,) = [c.children[-1] for c in tr.roots[0].children
                   if c.attrs.get("leg") == leg]
        counts = plan_counts(arrs.plan, kind)
        assert {k: span.attrs[k] for k in counts} == counts
        pad = explain_plan(arrs.plan, kind=kind)["padding"]
        assert counts["vpu_slots"] - counts["vpu_nnz"] \
            == pad["vpu_padded_zeros"]
        assert counts["tc_cells"] - counts["tc_nnz"] \
            == pad["tc_padded_zeros"]
        assert counts["vpu_slots"] == counts["vpu_segments"] * counts["cs"]
        assert counts["tc_nnz"] + counts["vpu_nnz"] == arrs.plan.nnz
        fetched = (int(np.asarray(arrs["vpu_seg_len"]).sum())
                   if "vpu_seg_len" in arrs else counts["vpu_slots"])
        assert counts["vpu_fetches"] == fetched <= counts["vpu_slots"]

    @pytest.mark.parametrize("segmented", [True, False],
                             ids=["segmented", "per_tile"])
    def test_vpu_fetches_counts_the_launched_lengths(self, segmented):
        from repro.core.formats import PlanArrays
        from repro.core.preprocess import preprocess_spmm
        from repro.obs.explain import plan_counts
        from repro.sparse.generate import power_law_csr
        from repro.tune import TuneConfig

        a = power_law_csr(96, 96, avg_row=6.0, seed=5)
        cfg = TuneConfig() if segmented else TuneConfig(ts=0, cs=0)
        plan = preprocess_spmm(a, cfg=cfg)
        arrs = PlanArrays(plan)
        counts = plan_counts(plan, "spmm")
        if segmented:
            fetched = int(np.asarray(arrs["vpu_seg_len"]).sum())
            assert counts["vpu_fetches"] == fetched == counts["vpu_nnz"]
            assert counts["vpu_fetches"] < counts["vpu_slots"]
        else:
            assert "vpu_seg_len" not in arrs
            assert counts["vpu_fetches"] == counts["vpu_slots"]

    def test_disabled_tracer_computes_no_counters(self, monkeypatch):
        from repro.core import preprocess
        from repro.obs import explain
        from repro.sparse.generate import power_law_csr

        def boom(*a, **k):
            raise AssertionError("counted with the tracer off")

        monkeypatch.setattr(explain, "plan_counts", boom)
        a = power_law_csr(64, 64, avg_row=5.0, seed=2)
        preprocess.preprocess_spmm(a)
        preprocess.preprocess_sddmm(a)


def test_enabled_spans_land_on_the_profiler_host_plane(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    on, off = Tracer(), Tracer(enabled=False)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with on.span("probe.enabled"):
            with on.span("probe.inner"):
                jnp.ones(4).block_until_ready()
        with off.span("probe.disabled"):
            jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    host = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host[ev.name] = (ev.start_ns, ev.duration_ns)
    assert "probe.enabled" in host and "probe.inner" in host
    assert "probe.disabled" not in host
    (s0, d0), (s1, d1) = host["probe.enabled"], host["probe.inner"]
    assert s0 <= s1 and s1 + d1 <= s0 + d0
    assert on.roots[0]._annotation is None    # closed with its span
