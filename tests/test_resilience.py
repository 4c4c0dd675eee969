"""Resilience layer: fault injection, degradation ladder, breakers,
deadlines, and plan-cache quarantine.

The load-bearing claims:

* under ANY injected fault pattern, every non-poisoned request completes
  **bit-identical** to a direct operator call — degradation trades
  throughput, never correctness;
* a poison request (non-finite inputs under ``validate=True``) fails
  alone with a typed result; its bucket neighbours are unharmed;
* circuit breakers open after N consecutive fast-path failures, serve
  degraded while open, and recover through half-open probes;
* deadline admission/drops and depth/deadline auto-flush account
  exactly (no silent loss, no double serve);
* a corrupt/tampered plan-cache file is quarantined and counted, never
  mistaken for a cold miss.

The chaos schedules are seeded (``REPRO_FAULT_SEED``) and replayable;
``hypothesis`` drives the storm property when installed, a seeded loop
otherwise.
"""
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sddmm import LibraSDDMM
from repro.core.spmm import LibraSpMM
from repro.kernels.ops import ApplyError, classify_apply_error
from repro.serve import (
    AdmissionError,
    DeadlineExceeded,
    ExecutionFailed,
    FaultPlan,
    FaultRule,
    GNNService,
    GraphRegistry,
    InjectedFault,
    ResiliencePolicy,
    ServeError,
    SparseEngine,
    corrupt_cache_entry,
)
from repro.sparse.generate import mixed_csr, power_law_csr
from repro.tune.cache import CACHE_VERSION, PlanCache
from repro.tune.model import TuneConfig

BASE_SEED = int(os.environ.get("REPRO_FAULT_SEED", "20260808"))
_NOSLEEP = lambda s: None                                    # noqa: E731


def _f32(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32))


def _engine(reg, **kw):
    kw.setdefault("sleep", _NOSLEEP)
    return SparseEngine(reg, **kw)


class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


# ------------------------------------------------------- classification ---
def test_classify_apply_error():
    assert classify_apply_error(
        ApplyError("compile", ("k",), ValueError("x"))) == "compile"
    assert classify_apply_error(
        InjectedFault(("g", "spmm", "fast"), 1)) == "injected"
    assert classify_apply_error(
        RuntimeError("RESOURCE_EXHAUSTED: out of memory")) == "resource"
    assert classify_apply_error(RuntimeError("non-finite output")) \
        == "nonfinite"
    assert classify_apply_error(ValueError("boom")) == "runtime"
    # execute-stage ApplyError classifies by its cause
    inner = InjectedFault(("g", "spmm", "fast"), 2, kind="resource")
    assert classify_apply_error(ApplyError("execute", ("k",), inner)) \
        == "resource"


# ---------------------------------------------------- degradation ladder ---
def test_fast_fault_degrades_to_singles_bit_identical(rng):
    a = mixed_csr(96, 80, seed=31)
    reg = GraphRegistry(max_graphs=2, width_buckets=(32,))
    reg.register(a, name="g", ops=("spmm",))
    plan = FaultPlan([FaultRule(kth=1, graph="g", op="spmm",
                                strategy="fast")])
    eng = _engine(reg, faults=plan)
    spmm = LibraSpMM(a, tune="model")
    bs = [_f32(rng, a.k, 32) for _ in range(3)]
    rids = [eng.submit("g", "spmm", b=b) for b in bs]
    out = eng.flush()
    for rid, b in zip(rids, bs):
        assert np.array_equal(np.asarray(out[rid]), np.asarray(spmm(b)))
    h = eng.health()
    assert h["degraded_served"]["single"] == 3
    assert h["failures"] == {"injected": 1}
    assert h["errors_returned"] == 0
    assert h["faults_injected"] == 1
    br = h["breakers"]["g/spmm"]
    assert br["state"] == "closed" and br["consecutive_failures"] == 1
    # the transient fault is spent: next flush rides the fast path again
    rid2 = eng.submit("g", "spmm", b=bs[0])
    out2 = eng.flush()
    assert np.array_equal(np.asarray(out2[rid2]),
                          np.asarray(spmm(bs[0])))
    h2 = eng.health()
    assert h2["degraded_served"]["single"] == 3        # unchanged
    assert h2["breakers"]["g/spmm"]["consecutive_failures"] == 0


def test_partial_fast_results_survive_mid_bucket_fault(rng):
    """A fault in sub-chunk #2 keeps sub-chunk #1's fast results; only
    the unserved remainder walks the ladder."""
    a = mixed_csr(96, 80, seed=32)
    reg = GraphRegistry(max_graphs=2, width_buckets=(32,),
                        panel_buckets=(1,))    # 1 request per fast apply
    reg.register(a, name="g", ops=("spmm",))
    plan = FaultPlan([FaultRule(kth=2, graph="g", op="spmm",
                                strategy="fast")])
    # max_panel=4 keeps all three requests in ONE bucket chunk while the
    # panel bucket of 1 forces one fast apply per request inside it
    eng = _engine(reg, faults=plan, max_panel=4)
    spmm = LibraSpMM(a, tune="model")
    bs = [_f32(rng, a.k, 32) for _ in range(3)]
    rids = [eng.submit("g", "spmm", b=b) for b in bs]
    out = eng.flush()
    for rid, b in zip(rids, bs):
        assert np.array_equal(np.asarray(out[rid]), np.asarray(spmm(b)))
    # request 1 was served fast before the fault; 2 and 3 degraded
    assert eng.health()["degraded_served"]["single"] == 2


def test_transient_fault_heals_with_backoff_retry(rng):
    a = mixed_csr(80, 64, seed=33)
    reg = GraphRegistry(max_graphs=2, width_buckets=(32,))
    reg.register(a, name="g", ops=("spmm",))
    plan = FaultPlan([
        FaultRule(kth=1, graph="g", op="spmm", strategy="fast"),
        FaultRule(kth=1, graph="g", op="spmm", strategy="single"),
    ])
    sleeps = []
    policy = ResiliencePolicy(backoff_base_s=0.001, backoff_cap_s=0.004)
    eng = SparseEngine(reg, resilience=policy, faults=plan,
                       sleep=sleeps.append)
    b = _f32(rng, a.k, 32)
    rid = eng.submit("g", "spmm", b=b)
    out = eng.flush()
    assert np.array_equal(np.asarray(out[rid]),
                          np.asarray(LibraSpMM(a, tune="model")(b)))
    # fast failed, single attempt 1 failed, backoff, attempt 2 healed
    assert sleeps == [0.001]
    h = eng.health()
    assert h["retries"] == 1 and h["retry_hist"] == {1: 1}
    assert h["degraded_served"]["single"] == 1
    assert h["failures"]["injected"] == 2


def test_ladder_exhausted_fails_alone_with_typed_result(rng):
    a1 = mixed_csr(96, 80, seed=34)
    a2 = power_law_csr(72, 96, 5.0, seed=35)
    reg = GraphRegistry(max_graphs=4, width_buckets=(32,))
    reg.register(a1, name="bad", ops=("spmm",))
    reg.register(a2, name="good", ops=("spmm",))
    # every strategy of `bad` latched broken, forever
    plan = FaultPlan([FaultRule(kth=1, graph="bad", times=-1)])
    eng = _engine(reg, resilience=ResiliencePolicy(attempts_per_rung=1))
    eng.faults = plan
    b1, b2 = _f32(rng, a1.k, 32), _f32(rng, a2.k, 32)
    rid_bad = eng.submit("bad", "spmm", b=b1)
    rid_good = eng.submit("good", "spmm", b=b2)
    out = eng.flush()
    assert np.array_equal(np.asarray(out[rid_good]),
                          np.asarray(LibraSpMM(a2, tune="model")(b2)))
    err = out[rid_bad]
    assert isinstance(err, ExecutionFailed)
    assert err.reason == "injected" and err.rid == rid_bad
    assert err.graph == "bad" and err.op == "spmm"
    assert eng.health()["errors_returned"] == 1


def test_resource_faults_classified_and_survived(rng):
    a = mixed_csr(80, 64, seed=36)
    reg = GraphRegistry(max_graphs=2, width_buckets=(32,))
    reg.register(a, name="g", ops=("spmm",))
    plan = FaultPlan([FaultRule(kth=1, graph="g", strategy="fast",
                                kind="resource")])
    eng = _engine(reg, faults=plan)
    b = _f32(rng, a.k, 32)
    rid = eng.submit("g", "spmm", b=b)
    out = eng.flush()
    assert np.array_equal(np.asarray(out[rid]),
                          np.asarray(LibraSpMM(a, tune="model")(b)))
    assert eng.health()["failures"] == {"resource": 1}


def test_sddmm_ladder_bit_identical(rng):
    a = mixed_csr(96, 96, seed=37)
    reg = GraphRegistry(max_graphs=2, width_buckets=(32,))
    reg.register(a, name="g")
    plan = FaultPlan([FaultRule(kth=1, graph="g", op="sddmm",
                                strategy="fast"),
                      FaultRule(kth=1, graph="g", op="sddmm",
                                strategy="single", times=-1)])
    eng = _engine(reg, faults=plan,
                  resilience=ResiliencePolicy(attempts_per_rung=1))
    x, y = _f32(rng, a.m, 32), _f32(rng, a.k, 32)
    rid = eng.submit("g", "sddmm", x=x, y=y)
    out = eng.flush()
    assert np.array_equal(np.asarray(out[rid]),
                          np.asarray(LibraSDDMM(a, tune="model")(x, y)))
    served = eng.health()["degraded_served"]
    assert served.get("single", 0) == 0       # single latched broken
    assert sum(served.values()) == 1          # a deeper rung answered


def test_pallas_backend_degraded_single_bit_identical(rng):
    a = mixed_csr(96, 80, seed=38)
    reg = GraphRegistry(max_graphs=2, width_buckets=(32,),
                        backend="pallas")
    reg.register(a, name="g", ops=("spmm",))
    plan = FaultPlan([FaultRule(kth=1, graph="g", strategy="fast")])
    eng = _engine(reg, faults=plan)
    spmm = LibraSpMM(a, tune="model")
    b = _f32(rng, a.k, 32)
    rid = eng.submit("g", "spmm", b=b)
    out = eng.flush()
    assert np.array_equal(np.asarray(out[rid]),
                          np.asarray(spmm(b, backend="pallas")))
    assert eng.health()["degraded_served"]["single"] == 1


def test_edge_vals_requests_degrade_bit_identical(rng):
    """The attention-serving path (per-request edge values) keeps its
    revalued bit-identity through the ladder."""
    from repro.kernels import ref
    from repro.kernels.ops import spmm_apply

    a = mixed_csr(96, 96, seed=39)
    reg = GraphRegistry(max_graphs=2, width_buckets=(32,))
    reg.register(a, name="g", ops=("spmm",))
    plan = FaultPlan([FaultRule(kth=1, graph="g", strategy="fast")])
    eng = _engine(reg, faults=plan)
    op = reg.resolve("g").op("spmm").op
    b, ev = _f32(rng, a.k, 32), _f32(rng, a.nnz)
    rid = eng.submit("g", "spmm", b=b, edge_vals=ev)
    out = eng.flush()
    arrs = ref.revalue_spmm_arrays(op.arrays, ev)
    direct = np.asarray(spmm_apply(arrs, b, m=op.m, nwin=op.nwin,
                                   backend="xla", cfg=op.tune_config))
    assert np.array_equal(np.asarray(out[rid]), direct)
    assert eng.health()["degraded_served"]["single"] == 1


# ------------------------------------------------------------ validation ---
def test_validate_catches_injected_nan_and_heals(rng):
    a = mixed_csr(80, 64, seed=40)
    reg = GraphRegistry(max_graphs=2, width_buckets=(32,))
    reg.register(a, name="g", ops=("spmm",))
    plan = FaultPlan([FaultRule(kth=1, graph="g", strategy="fast",
                                kind="nan")])
    eng = _engine(reg, faults=plan,
                  resilience=ResiliencePolicy(validate=True))
    b = _f32(rng, a.k, 32)
    rid = eng.submit("g", "spmm", b=b)
    out = eng.flush()
    assert np.array_equal(np.asarray(out[rid]),
                          np.asarray(LibraSpMM(a, tune="model")(b)))
    assert eng.health()["failures"] == {"nonfinite": 1}


def test_without_validate_nan_flows_through(rng):
    """validate=False is the default hot-path contract: silent numeric
    corruption is the caller's problem (documented opt-in)."""
    a = mixed_csr(80, 64, seed=41)
    reg = GraphRegistry(max_graphs=2, width_buckets=(32,))
    reg.register(a, name="g", ops=("spmm",))
    plan = FaultPlan([FaultRule(kth=1, graph="g", strategy="fast",
                                kind="nan")])
    eng = _engine(reg, faults=plan)
    rid = eng.submit("g", "spmm", b=_f32(rng, a.k, 32))
    out = eng.flush()
    assert not isinstance(out[rid], ServeError)
    assert not bool(jnp.all(jnp.isfinite(out[rid])))
    assert eng.health()["failures"] == {}


def test_poison_request_fails_alone_under_validate(rng):
    """One all-NaN submission in a packed bucket: its neighbours come
    back bit-identical, it alone exhausts the ladder as `nonfinite`."""
    a = mixed_csr(96, 80, seed=42)
    reg = GraphRegistry(max_graphs=2, width_buckets=(32,))
    reg.register(a, name="g", ops=("spmm",))
    eng = _engine(reg, resilience=ResiliencePolicy(validate=True,
                                                   attempts_per_rung=1))
    spmm = LibraSpMM(a, tune="model")
    good = [_f32(rng, a.k, 32) for _ in range(2)]
    bad = jnp.full((a.k, 32), jnp.nan)
    rids = [eng.submit("g", "spmm", b=b) for b in good]
    rid_bad = eng.submit("g", "spmm", b=bad)
    out = eng.flush()
    for rid, b in zip(rids, good):
        assert np.array_equal(np.asarray(out[rid]), np.asarray(spmm(b)))
    err = out[rid_bad]
    assert isinstance(err, ExecutionFailed) and err.reason == "nonfinite"
    assert eng.health()["degraded_served"]["single"] == 2


# ---------------------------------------------------------- circuit breaker ---
def test_breaker_open_probe_reopen_recover(rng):
    a = mixed_csr(80, 64, seed=43)
    reg = GraphRegistry(max_graphs=2, width_buckets=(32,))
    reg.register(a, name="g", ops=("spmm",))
    plan = FaultPlan([FaultRule(kth=1, graph="g", strategy="fast",
                                times=3)])
    policy = ResiliencePolicy(breaker_threshold=2, probe_after=2,
                              attempts_per_rung=1)
    eng = _engine(reg, resilience=policy, faults=plan)
    spmm = LibraSpMM(a, tune="model")

    def one_flush():
        b = _f32(rng, a.k, 32)
        rid = eng.submit("g", "spmm", b=b)
        out = eng.flush()
        assert np.array_equal(np.asarray(out[rid]), np.asarray(spmm(b)))

    def state():
        return eng.health()["breakers"]["g/spmm"]

    one_flush()                               # fast fault #1 → degraded
    assert state()["state"] == "closed"
    one_flush()                               # fault #2 → threshold: open
    assert state()["state"] == "open" and state()["opened"] == 1
    one_flush()                               # open tick 1: fast skipped
    assert eng.health()["breaker_skips"] == 1
    one_flush()                # tick 2 → half-open probe → fault #3 → reopen
    s = state()
    assert s["state"] == "open" and s["reopened"] == 1 and s["probes"] == 1
    one_flush()                               # open tick 1 again: skipped
    one_flush()                     # probe again → faults spent → recover
    s = state()
    assert s["state"] == "closed"
    assert s["recoveries"] == 1 and s["probes"] == 2
    one_flush()                               # steady-state fast again
    assert state()["consecutive_failures"] == 0
    assert eng.health()["breaker_skips"] == 2


# ------------------------------------------------------------- deadlines ---
def test_infeasible_deadline_rejected_typed(rng):
    a = mixed_csr(64, 48, seed=44)
    reg = GraphRegistry(max_graphs=2, width_buckets=(32,))
    reg.register(a, name="g", ops=("spmm",))
    eng = _engine(reg, resilience=ResiliencePolicy(min_deadline_ms=2.0))
    b = _f32(rng, a.k, 32)
    for bad_dl in (0.0, -5.0, 1.0):           # ≤0 or below the floor
        with pytest.raises(AdmissionError) as ei:
            eng.submit("g", "spmm", b=b, deadline_ms=bad_dl)
        assert ei.value.reason == "infeasible_deadline"
    rid = eng.submit("g", "spmm", b=b, deadline_ms=50.0)
    assert eng.stats()["rejected"] == {"infeasible_deadline": 3}
    out = eng.flush()
    assert not isinstance(out[rid], ServeError)
    # docstring reason list stays in sync with what the engine raises
    assert "infeasible_deadline" in AdmissionError.__doc__


def test_deadline_storm_drops_exactly_the_expired(rng):
    a = mixed_csr(96, 80, seed=45)
    reg = GraphRegistry(max_graphs=2, width_buckets=(32,))
    reg.register(a, name="g", ops=("spmm",))
    clk = _Clock()
    eng = _engine(reg, clock=clk)
    spmm = LibraSpMM(a, tune="model")
    bs = [_f32(rng, a.k, 32) for _ in range(5)]
    doomed = [eng.submit("g", "spmm", b=b, deadline_ms=5.0)
              for b in bs[:3]]
    safe = [eng.submit("g", "spmm", b=b) for b in bs[3:]]
    clk.t += 0.1                              # 100ms pass: 5ms deadlines die
    out = eng.flush()
    for rid in doomed:
        assert isinstance(out[rid], DeadlineExceeded)
        assert out[rid].reason == "deadline_exceeded"
    for rid, b in zip(safe, bs[3:]):
        assert np.array_equal(np.asarray(out[rid]), np.asarray(spmm(b)))
    h = eng.health()["deadline"]
    assert h == {"submitted": 3, "misses": 3, "miss_rate": 1.0,
                 "infeasible_rejected": 0}
    # breakers untouched: a deadline drop is not an executable failure
    assert eng.health()["breakers"]["g/spmm"]["consecutive_failures"] == 0


def test_autoflush_on_depth_and_deadline_slack(rng):
    a = mixed_csr(80, 64, seed=46)
    reg = GraphRegistry(max_graphs=2, width_buckets=(32,))
    reg.register(a, name="g", ops=("spmm",))
    spmm = LibraSpMM(a, tune="model")
    # depth trigger
    eng = _engine(reg, flush_at_depth=2)
    bs = [_f32(rng, a.k, 32) for _ in range(2)]
    rids = [eng.submit("g", "spmm", b=b) for b in bs]
    assert eng.queue_depth == 0               # drained at depth 2
    assert eng.health()["autoflushes"] == {"depth": 1}
    out = eng.flush()                         # redeposited results
    for rid, b in zip(rids, bs):
        assert np.array_equal(np.asarray(out[rid]), np.asarray(spmm(b)))
    # deadline-slack trigger
    clk = _Clock()
    eng2 = _engine(reg, flush_slack_ms=50.0, clock=clk)
    rid = eng2.submit("g", "spmm", b=bs[0], deadline_ms=10.0)
    assert eng2.queue_depth == 0              # 10ms ≤ 50ms slack: flushed
    assert eng2.health()["autoflushes"] == {"deadline": 1}
    out = eng2.flush()
    assert np.array_equal(np.asarray(out[rid]), np.asarray(spmm(bs[0])))


# ------------------------------------------- partial results, no resilience ---
def test_flush_returns_partial_results_without_resilience(rng):
    """Satellite contract: even with the ladder disabled, a failing
    bucket yields typed per-request errors, not a lost flush."""
    a1 = mixed_csr(96, 80, seed=47)
    a2 = power_law_csr(72, 96, 5.0, seed=48)
    reg = GraphRegistry(max_graphs=4, width_buckets=(32,))
    reg.register(a1, name="bad", ops=("spmm",))
    reg.register(a2, name="good", ops=("spmm",))
    plan = FaultPlan([FaultRule(kth=1, graph="bad", strategy="fast",
                                times=-1)])
    eng = _engine(reg, resilience=False, faults=plan)
    b1, b2 = _f32(rng, a1.k, 32), _f32(rng, a2.k, 32)
    rid_bad = eng.submit("bad", "spmm", b=b1)
    rid_good = eng.submit("good", "spmm", b=b2)
    out = eng.flush()
    assert np.array_equal(np.asarray(out[rid_good]),
                          np.asarray(LibraSpMM(a2, tune="model")(b2)))
    err = out[rid_bad]
    assert isinstance(err, ExecutionFailed) and err.reason == "injected"
    h = eng.health()
    assert not h["resilience_enabled"]
    assert h["degraded_served"] == {} and h["breakers"] == {}


# ------------------------------------------------------------ warm faults ---
def test_warmup_compile_faults_are_schedulable():
    a = mixed_csr(80, 64, seed=49)
    plan = FaultPlan([FaultRule(kth=1, strategy="warm")])
    reg = GraphRegistry(max_graphs=2, width_buckets=(16,),
                        panel_buckets=(1,), faults=plan)
    with pytest.raises(InjectedFault):
        reg.register(a, name="g", ops=("spmm",), warm_widths=(16,))


# ------------------------------------------------------ GNN service errors ---
def test_gnn_service_scoring_fails_alone(rng):
    from repro.models import gnn as mgnn
    import jax

    a = mixed_csr(96, 96, seed=50)
    reg = GraphRegistry(max_graphs=4)
    eng = _engine(reg, resilience=ResiliencePolicy(validate=True,
                                                   attempts_per_rung=1))
    svc = GNNService(eng)
    params = mgnn.init_gcn(jax.random.PRNGKey(0), [32, 32, 8])
    svc.register_gcn("gcn", a, params)
    feats = _f32(rng, a.m, 32)
    s_good = svc.submit("gcn", feats)
    s_bad = svc.submit("gcn", jnp.full((a.m, 32), jnp.nan))
    res = svc.flush()
    g = mgnn.GraphOps(a, tune="model")
    want = np.asarray(mgnn.gcn_forward(
        params, g, feats, jnp.asarray(mgnn.gcn_norm_edges(a))))
    np.testing.assert_allclose(np.asarray(res[s_good]), want,
                               rtol=1e-4, atol=1e-5)
    err = res[s_bad]
    assert isinstance(err, ServeError) and err.reason == "nonfinite"
    # single-request convenience raises the typed error
    with pytest.raises(ServeError):
        svc.score("gcn", jnp.full((a.m, 32), jnp.nan))


# -------------------------------------------------------- cache quarantine ---
def test_cache_quarantine_roundtrip(tmp_path):
    pc = PlanCache(str(tmp_path), max_entries=8)
    cfg = TuneConfig(nt=128, threshold=4, source="search")
    pc.put("k1", cfg)
    assert pc.get("k1") == cfg.replace(source="cache")
    # torn write → unparseable → quarantined, not a silent miss
    path = corrupt_cache_entry(pc, "k1", mode="garbage")
    assert pc.get("k1") is None
    assert not os.path.exists(path)
    assert os.path.exists(os.path.join(pc.quarantine_dir, "k1.json"))
    # tampered config with stale checksum → quarantined too
    pc.put("k1", cfg)
    corrupt_cache_entry(pc, "k1", mode="tamper")
    assert pc.get("k1") is None
    st = pc.stats()
    assert st["quarantined"] == 2
    assert st["quarantined_by_reason"] == {"unparseable": 1,
                                           "checksum_mismatch": 1}
    assert st["quarantine_dir_files"] == 1    # same name, overwritten
    # a re-put heals: round-trips again, quarantine count untouched
    pc.put("k1", cfg)
    assert pc.get("k1") == cfg.replace(source="cache")
    assert pc.stats()["quarantined"] == 2
    assert pc.size() == 1                     # quarantine dir not counted


def test_cache_version_skew_is_silent_miss_not_quarantine(tmp_path):
    import json

    pc = PlanCache(str(tmp_path), max_entries=8)
    pc.put("k", TuneConfig(nt=256))
    p = pc._path("k")
    with open(p) as f:
        doc = json.load(f)
    doc["version"] = CACHE_VERSION - 1        # stale format, intact file
    with open(p, "w") as f:
        json.dump(doc, f)
    assert pc.get("k") is None
    assert pc.stats()["quarantined"] == 0 and os.path.exists(p)


# ------------------------------------------------------------ chaos storm ---
_STORM = {}


def _storm_ctx():
    """Shared fixtures for the storm property (built once: registering
    and tuning per example would swamp the suite)."""
    if not _STORM:
        rng = np.random.default_rng(BASE_SEED)
        a1 = mixed_csr(96, 80, seed=51)
        a2 = power_law_csr(72, 96, 5.0, seed=52)
        reg = GraphRegistry(max_graphs=4, width_buckets=(32,))
        reg.register(a1, name="g1", ops=("spmm",))
        reg.register(a2, name="g2")
        spmm1 = LibraSpMM(a1, tune="model")
        spmm2 = LibraSpMM(a2, tune="model")
        sddmm2 = LibraSDDMM(a2, tune="model")
        subs, want = [], []
        for _ in range(3):
            b = _f32(rng, a1.k, 32)
            subs.append(("g1", "spmm", {"b": b}))
            want.append(np.asarray(spmm1(b)))
        for _ in range(2):
            b = _f32(rng, a2.k, 32)
            subs.append(("g2", "spmm", {"b": b}))
            want.append(np.asarray(spmm2(b)))
        x, y = _f32(rng, a2.m, 32), _f32(rng, a2.k, 32)
        subs.append(("g2", "sddmm", {"x": x, "y": y}))
        want.append(np.asarray(sddmm2(x, y)))
        sites = [(g, op, s)
                 for g, op in (("g1", "spmm"), ("g2", "spmm"),
                               ("g2", "sddmm"))
                 for s in ("fast", "single", "unsegmented", "xla")]
        _STORM.update(reg=reg, subs=subs, want=want, sites=sites)
    return _STORM


def _run_storm(seed: int) -> None:
    """Property: under an arbitrary seeded fault schedule, every request
    either completes bit-identical to its direct call or fails with a
    typed ServeError — never silently wrong, never lost."""
    ctx = _storm_ctx()
    plan = FaultPlan.storm(seed, ctx["sites"], n_faults=6, max_k=4,
                           kinds=("raise", "resource"), times=(1, 2, -1))
    eng = _engine(ctx["reg"], faults=plan,
                  resilience=ResiliencePolicy(attempts_per_rung=2))
    rids = [eng.submit(g, op, **kw) for g, op, kw in ctx["subs"]]
    out = eng.flush()
    assert sorted(out) == sorted(rids)        # nothing lost, nothing extra
    failed = 0
    for rid, want in zip(rids, ctx["want"]):
        got = out[rid]
        if isinstance(got, ServeError):
            assert got.reason in ("injected", "resource", "runtime")
            assert got.rid == rid
            failed += 1
        else:
            assert np.array_equal(np.asarray(got), want)
    h = eng.health()
    assert h["errors_returned"] == failed
    if plan.log:
        assert h["failures"] or failed == 0 or h["degraded_served"]
    # the engine survives the storm: a clean engine serves again
    eng2 = _engine(ctx["reg"])
    rids2 = [eng2.submit(g, op, **kw) for g, op, kw in ctx["subs"]]
    out2 = eng2.flush()
    for rid, want in zip(rids2, ctx["want"]):
        assert np.array_equal(np.asarray(out2[rid]), want)


try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st_

    @settings(max_examples=10, deadline=None,
              suppress_health_check=list(HealthCheck))
    @given(st_.integers(min_value=0, max_value=2**16 - 1))
    def test_fault_storm_property(seed):
        _run_storm(seed)
except ImportError:                            # seeded-loop fallback
    @pytest.mark.parametrize("offset", range(10))
    def test_fault_storm_property(offset):
        _run_storm((BASE_SEED + offset) % 2**16)


def test_storm_is_replayable():
    """Same seed ⇒ same schedule ⇒ same fired-fault log."""
    ctx = _storm_ctx()
    logs = []
    for _ in range(2):
        plan = FaultPlan.storm(BASE_SEED, ctx["sites"], n_faults=5)
        eng = _engine(ctx["reg"], faults=plan)
        for g, op, kw in ctx["subs"]:
            eng.submit(g, op, **kw)
        eng.flush()
        logs.append(list(plan.log))
    assert logs[0] == logs[1]
