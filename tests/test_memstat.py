"""Device-memory observability: lazy PlanArrays views, the MemLedger,
byte-budget eviction, MemoryPressure admission, and the /memory route.

Ground truth everywhere is ``jax.Array.nbytes``: the ledger's numbers
must match sums of actually-uploaded array bytes exactly, never
estimates.
"""
import json
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

jnp = jax.numpy

from repro.core.formats import PLAN_VIEWS, PlanArrays, view_of_key
from repro.core.preprocess import preprocess_sddmm, preprocess_spmm
from repro.core.windows import num_windows
from repro.kernels import ref
from repro.kernels.ops import sddmm_apply, spmm_apply
from repro.obs.memstat import MemLedger, MemoryPressure, render_memory
from repro.obs.metrics import MetricsRegistry
from repro.sparse import power_law_csr, suitesparse_like_corpus


@pytest.fixture(scope="module")
def corpus():
    return suitesparse_like_corpus(n_small=4, seed=7)


def _resident_sum(pa: PlanArrays) -> int:
    return sum(int(v.nbytes) for _, v in pa.resident_items())


# --------------------------------------------------------- lazy views ---
class TestLazyBitIdentity:
    @pytest.mark.parametrize("backend", ["xla", "pallas"])
    def test_spmm_lazy_vs_eager(self, corpus, backend):
        rng = np.random.default_rng(0)
        for a in corpus.values():
            plan = preprocess_spmm(a)
            pa = PlanArrays(plan)
            nwin = num_windows(a.shape[0])
            b = rng.standard_normal((a.shape[1], 16)).astype(np.float32)
            eager = dict(PlanArrays(plan).materialize_all())
            y_e = spmm_apply(eager, jnp.asarray(b), m=a.shape[0],
                             nwin=nwin, backend=backend, interpret=True)
            lazy = pa.apply_arrays()
            # both backends read the same segment tables
            assert "vpu_seg_len" in lazy
            y_l = spmm_apply(lazy, jnp.asarray(b),
                             m=a.shape[0], nwin=nwin, backend=backend,
                             interpret=True)
            assert np.array_equal(np.asarray(y_e), np.asarray(y_l))
            # the apply view resident set is a strict subset
            assert _resident_sum(pa) < pa.projected_nbytes()

    @pytest.mark.parametrize("backend", ["xla", "pallas"])
    def test_sddmm_lazy_vs_eager(self, corpus, backend):
        rng = np.random.default_rng(1)
        for a in corpus.values():
            plan = preprocess_sddmm(a)
            pa = PlanArrays(plan)
            x = rng.standard_normal((a.shape[0], 16)).astype(np.float32)
            y = rng.standard_normal((a.shape[1], 16)).astype(np.float32)
            eager = dict(PlanArrays(plan).materialize_all())
            o_e = sddmm_apply(eager, jnp.asarray(x), jnp.asarray(y),
                              nnz=plan.nnz, backend=backend,
                              interpret=True)
            o_l = sddmm_apply(pa.apply_arrays(), jnp.asarray(x),
                              jnp.asarray(y), nnz=plan.nnz,
                              backend=backend, interpret=True)
            assert np.array_equal(np.asarray(o_e), np.asarray(o_l))

    def test_revalue_view_lazy(self, corpus):
        """edge_vals serving with the revalue view (pos maps instead of
        baked-in values) matches eager revaluation bitwise."""
        a = next(iter(corpus.values()))
        plan = preprocess_spmm(a)
        nwin = num_windows(a.shape[0])
        rng = np.random.default_rng(2)
        b = rng.standard_normal((a.shape[1], 8)).astype(np.float32)
        ev = rng.standard_normal(a.nnz).astype(np.float32)
        eager = dict(PlanArrays(plan).materialize_all())
        y_e = spmm_apply(ref.revalue_spmm_arrays(eager, jnp.asarray(ev)),
                         jnp.asarray(b), m=a.shape[0], nwin=nwin,
                         backend="xla", interpret=True)
        pa = PlanArrays(plan)
        lazy = pa.apply_arrays(revalue=True)
        assert not any(k.endswith("_vals") for k in lazy)
        y_l = spmm_apply(ref.revalue_spmm_arrays(lazy, jnp.asarray(ev)),
                         jnp.asarray(b), m=a.shape[0], nwin=nwin,
                         backend="xla", interpret=True)
        assert np.array_equal(np.asarray(y_e), np.asarray(y_l))

    def test_pytree_flatten_is_eager_dict(self, corpus):
        """Call sites that jit over op.arrays directly: flattening
        must materialize every key, eager-equivalently."""
        a = next(iter(corpus.values()))
        pa = PlanArrays(preprocess_spmm(a))
        leaves, treedef = jax.tree_util.tree_flatten(pa)
        assert len(leaves) == len(pa)
        rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
        assert isinstance(rebuilt, dict)
        assert set(rebuilt) == set(pa)
        assert pa.resident_nbytes() == pa.projected_nbytes()

    def test_view_classification(self):
        assert PLAN_VIEWS == ("segment", "revalue")
        assert view_of_key("tc_seg_pos") == "revalue"
        assert view_of_key("vpu_seg_pos") == "revalue"
        assert view_of_key("tc_seg_vals") == "segment"
        # SDDMM scatter maps are structural, not revalue
        assert view_of_key("tc_seg_out_pos") == "segment"
        assert view_of_key("vpu_seg_out_pos") == "segment"

    def test_vpu_seg_len_in_pallas_segment_view_only(self, corpus):
        a = next(iter(corpus.values()))
        pa = PlanArrays(preprocess_spmm(a))
        assert view_of_key("vpu_seg_len") == "segment"
        assert "vpu_seg_len" in pa.apply_keys()
        assert "vpu_seg_len" in pa.apply_keys(revalue=True)
        assert "vpu_seg_len" not in PlanArrays(
            preprocess_sddmm(a)).apply_keys()
        nbytes = int(pa._host["vpu_seg_len"].nbytes)
        assert nbytes == 4 * pa._host["vpu_seg_row"].shape[0]
        assert pa.projected_nbytes(pa.apply_keys()) == sum(
            int(pa._host[k].nbytes) for k in pa.apply_keys())
        pa.apply_arrays()
        assert pa._uploads["vpu_seg_len"] == ("segment", nbytes, "int32")
        assert pa.resident_nbytes() == pa.projected_nbytes(pa.apply_keys())

    def test_tc_bitmap_not_in_spmm_backend_views(self, corpus):
        a = next(iter(corpus.values()))
        pa = PlanArrays(preprocess_spmm(a))
        for revalue in (False, True):
            assert not any("bitmap" in k
                           for k in pa.apply_keys(revalue=revalue))


# ------------------------------------------------------------- ledger ---
class TestMemLedgerExactness:
    def test_ledger_matches_nbytes_exactly(self, corpus):
        m = MetricsRegistry()
        led = MemLedger(metrics=m)
        pas = {}
        for name, a in corpus.items():
            pa = PlanArrays(preprocess_spmm(a))
            pa.set_accountant(led.binder(name, "spmm"))
            pa.apply_arrays()
            pas[name] = pa
        expect = sum(_resident_sum(pa) for pa in pas.values())
        assert led.resident_bytes() == expect
        rep = led.memory_report()
        assert rep["resident_bytes"] == expect
        assert sum(rep["by_view"].values()) == expect
        assert sum(rep["by_op"].values()) == expect
        assert sum(g["bytes"] for g in rep["graphs"]) == expect
        # materialize more: ledger tracks the growth exactly
        next(iter(pas.values())).apply_arrays(revalue=True)
        expect = sum(_resident_sum(pa) for pa in pas.values())
        assert led.resident_bytes() == expect
        assert led.peak_bytes() == expect

    def test_replay_on_late_attach(self, corpus):
        """tune='search' can materialize before the registry attaches
        accounting; set_accountant replays recorded uploads."""
        a = next(iter(corpus.values()))
        pa = PlanArrays(preprocess_spmm(a))
        pa.apply_arrays()   # uploads happen before any accountant
        led = MemLedger()
        pa.set_accountant(led.binder("g", "spmm"))
        assert led.resident_bytes() == _resident_sum(pa)

    def test_mixed_backend_double_materialization(self, corpus):
        """Serving one graph through both apply views accounts each
        array once (delta semantics), totals still exact."""
        a = next(iter(corpus.values()))
        pa = PlanArrays(preprocess_spmm(a))
        led = MemLedger()
        pa.set_accountant(led.binder("g", "spmm"))
        pa.apply_arrays()
        pa.apply_arrays(revalue=True)
        pa.apply_arrays()   # re-serving re-uses, no double count
        assert led.resident_bytes() == _resident_sum(pa)
        assert led.graph_bytes("g") == _resident_sum(pa)
        vb = pa.view_nbytes()
        for view in PLAN_VIEWS:
            assert led.resident_bytes(view) == vb[view]

    def test_release_and_render(self, corpus):
        led = MemLedger()
        a = next(iter(corpus.values()))
        pa = PlanArrays(preprocess_spmm(a))
        pa.set_accountant(led.binder("g", "spmm"))
        pa.materialize_all()
        total = led.resident_bytes()
        assert total > 0
        freed = led.release("g")
        assert freed == total
        assert led.resident_bytes() == 0
        assert led.peak_bytes() == total
        rep = led.memory_report()
        assert rep["evicted_bytes"] == total
        text = render_memory(rep)
        assert "memory report" in text and "evicted" in text

    def test_metrics_series_materialized_at_zero(self):
        m = MetricsRegistry()
        MemLedger(metrics=m)
        body = m.exposition()
        for view in PLAN_VIEWS:
            assert f'registry_resident_bytes{{view="{view}"}} 0' in body
        assert "registry_bytes_evicted_total 0" in body


# --------------------------------------------------- registry + engine ---
class TestByteBudget:
    def _sizes(self, graphs, reg):
        from repro.serve.registry import graph_key
        return {n: reg.mem.graph_bytes(
            graph_key(a, "hybrid", "batched"))
            for n, a in graphs}

    def test_lru_eviction_determinism(self):
        """Injected sizes: serving order fixes LRU order, eviction
        drops exactly the least-recently-served graphs."""
        from repro.serve import GraphRegistry

        reg = GraphRegistry(max_graphs=8, width_buckets=(8,),
                            panel_buckets=(1,))
        graphs = [(f"g{i}", power_law_csr(64, 64, 4.0, seed=i))
                  for i in range(3)]
        for n, a in graphs:
            reg.register(a, name=n, ops=("spmm",))
        rng = np.random.default_rng(0)
        # serve g0, g1, g2 in order → LRU order is g0 < g1 < g2
        for n, a in graphs:
            b = rng.standard_normal((a.shape[1], 8)).astype(np.float32)
            reg.get(n).op("spmm")(jnp.asarray(b)[None])
        sizes = [reg.mem.graph_bytes(reg.resolve(n).key)
                 for n, _ in graphs]
        assert all(s > 0 for s in sizes)
        # budget that keeps exactly the two most recently served
        reg.max_bytes = sizes[1] + sizes[2]
        dropped = reg.enforce_budget()
        assert dropped == 1
        assert "g0" not in reg and "g1" in reg and "g2" in reg
        assert reg.mem.resident_bytes() == sizes[1] + sizes[2]
        assert reg.stats()["pressure_evictions"] == 1
        # an over-budget lone survivor is never evicted
        reg.max_bytes = 1
        assert reg.enforce_budget() == 1
        assert len(reg.stats()["names"]) == 1

    def test_memory_pressure_typed_reject(self):
        from repro.serve import GraphRegistry, SparseEngine

        reg = GraphRegistry(max_graphs=4, max_bytes=64)
        eng = SparseEngine(reg)
        a = power_law_csr(64, 64, 4.0, seed=0)
        with pytest.raises(MemoryPressure) as ei:
            eng.register(a, name="big", ops=("spmm",))
        assert ei.value.reason == "memory_pressure"
        assert ei.value.required > ei.value.budget == 64
        assert eng._rejected.series()["memory_pressure"] == 1
        assert reg.stats()["pressure_rejects"] == 1
        assert "big" not in reg

    def test_env_var_budget(self, monkeypatch):
        from repro.serve import GraphRegistry

        monkeypatch.setenv("REPRO_REGISTRY_MAX_BYTES", "12345")
        assert GraphRegistry(max_graphs=2).max_bytes == 12345
        monkeypatch.delenv("REPRO_REGISTRY_MAX_BYTES")
        assert GraphRegistry(max_graphs=2).max_bytes is None

    def test_engine_flush_enforces_budget(self):
        from repro.serve import GraphRegistry, SparseEngine

        reg = GraphRegistry(max_graphs=8, width_buckets=(8,),
                            panel_buckets=(1,))
        eng = SparseEngine(reg)
        graphs = [(f"g{i}", power_law_csr(64, 64, 4.0, seed=10 + i))
                  for i in range(3)]
        rng = np.random.default_rng(0)
        for n, a in graphs:
            eng.register(a, name=n, ops=("spmm",))
        # serve all three, then shrink the budget: the next flush evicts
        for n, a in graphs:
            b = rng.standard_normal((a.shape[1], 8)).astype(np.float32)
            eng.submit(n, "spmm", b=jnp.asarray(b))
        eng.flush()
        assert reg.stats()["graphs_resident"] == 3
        reg.max_bytes = reg.mem.resident_bytes() - 1
        b = rng.standard_normal(
            (graphs[2][1].shape[1], 8)).astype(np.float32)
        rid = eng.submit("g2", "spmm", b=jnp.asarray(b))
        out = eng.flush()
        assert not isinstance(out[rid], Exception)
        assert reg.mem.resident_bytes() <= reg.max_bytes
        assert reg.stats()["graphs_resident"] < 3

    def test_eviction_releases_and_rebuild_reaccounts(self):
        from repro.serve import GraphRegistry

        reg = GraphRegistry(max_graphs=1, width_buckets=(8,),
                            panel_buckets=(1,))
        a0 = power_law_csr(64, 64, 4.0, seed=0)
        a1 = power_law_csr(64, 64, 4.0, seed=1)
        reg.register(a0, name="g0", ops=("spmm",))
        rng = np.random.default_rng(0)
        b = rng.standard_normal((64, 8)).astype(np.float32)
        reg.get("g0").op("spmm")(jnp.asarray(b)[None])
        assert reg.mem.resident_bytes() > 0
        reg.register(a1, name="g1", ops=("spmm",))   # count-cap evicts g0
        assert "g0" not in reg
        rep = reg.memory_report()
        assert rep["evicted_bytes"] > 0
        reg.get("g1").op("spmm")(jnp.asarray(b)[None])
        assert reg.mem.resident_bytes() == reg.mem.graph_bytes(
            reg.resolve("g1").key)

    def test_mem_false_disables_accounting(self):
        from repro.serve import GraphRegistry

        reg = GraphRegistry(max_graphs=2, mem=False)
        assert reg.mem is None
        reg.register(power_law_csr(64, 64, 4.0, seed=0), name="g",
                     ops=("spmm",))
        with pytest.raises(ValueError):
            reg.memory_report()


# ------------------------------------------------- http + explain + cal ---
class TestMemoryObservability:
    def test_http_memory_and_metrics(self):
        from repro.serve import GraphRegistry, SparseEngine

        a = power_law_csr(128, 96, 6.0, seed=3)
        reg = GraphRegistry(max_graphs=4, width_buckets=(16,),
                            panel_buckets=(1, 2))
        eng = SparseEngine(reg)
        eng.register(a, name="g", ops=("spmm",))
        b = np.random.default_rng(0).standard_normal(
            (96, 16)).astype(np.float32)
        eng.submit("g", "spmm", b=b)
        eng.flush()

        with eng.serve_http() as srv:
            doc = json.loads(urllib.request.urlopen(
                f"{srv.url}/memory", timeout=10).read().decode())
            assert doc["kind"] == "memory_report"
            assert doc["resident_bytes"] == reg.mem.resident_bytes() > 0
            assert doc["n_graphs"] == 1
            body = urllib.request.urlopen(
                f"{srv.url}/metrics", timeout=10).read().decode()
            assert 'registry_resident_bytes{view="segment"}' in body
            assert "registry_bytes_evicted_total" in body
            # route list advertises /memory
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{srv.url}/bogus", timeout=10)
            assert "/memory" in ei.value.read().decode()

    def test_http_memory_404_when_disabled(self):
        from repro.serve import GraphRegistry, SparseEngine

        eng = SparseEngine(GraphRegistry(max_graphs=2, mem=False))
        with eng.serve_http() as srv:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{srv.url}/memory", timeout=10)
            assert ei.value.code == 404

    def test_explain_memory_section(self):
        from repro.obs.explain import explain_spmm, render_table
        from repro.core.spmm import LibraSpMM

        a = power_law_csr(128, 96, 6.0, seed=3)
        op = LibraSpMM(a)
        report = explain_spmm(op)
        mem = report["memory"]
        assert mem["resident_bytes"] == 0          # nothing served yet
        op(np.zeros((96, 8), np.float32), backend="xla")
        report = explain_spmm(op)
        mem = report["memory"]
        assert mem["resident_bytes"] == op.arrays.resident_nbytes() > 0
        assert mem["views"]["segment"]["resident_keys"] > 0
        text = render_table(report)
        assert "mem_segment" in text and "mem_resident" in text

    def test_ledger_samples_carry_mem_bytes(self, tmp_path):
        from repro.core.spmm import LibraSpMM
        from repro.obs.calibrate import calibration_report
        from repro.obs.ledger import PerfLedger, use_ledger

        a = power_law_csr(128, 96, 6.0, seed=3)
        led = PerfLedger(str(tmp_path))
        with use_ledger(led):
            op = LibraSpMM(a)
            op(np.zeros((96, 8), np.float32), backend="xla")
        samples = led.samples()
        assert samples
        mem = samples[-1]["mem_bytes"]
        assert mem["total"] == sum(
            mem[v] for v in PLAN_VIEWS)
        assert mem["total"] == op.arrays.resident_nbytes()
        rep = calibration_report(led)
        assert any(k.startswith("spmm/mem-") for k in rep["footprints"])

    def test_calibration_report_tolerates_old_samples(self):
        from repro.obs.calibrate import calibration_report, \
            render_calibration

        # pre-PR-9 sample without mem_bytes
        s = {"key": "k", "op": "spmm", "backend": "xla", "tc_frac": 0.5,
             "wall_s": 1e-4, "predicted_s": 1e-4}
        rep = calibration_report([s])
        assert rep["footprints"] == {}
        assert "geomean" in render_calibration(rep)
        # pre-PR-9 persisted report without the footprints key
        del rep["footprints"]
        assert "geomean" in render_calibration(rep)
