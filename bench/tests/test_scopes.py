"""The operator-scope reduction and its readers, on hand-made traces and
span trees and on a recorded chip trace."""
import json
from pathlib import Path

import pytest

from bench import counts, scopes, tracing

DATA = Path(__file__).parent / "data"
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("op_name, path", [
    ("jit(gcn_train_step)/jvp(spmm)/jit(spmm_apply)/vpu/jit(spmm_vpu)/"
     "spmm_vpu/pallas_call",
     ("gcn_train_step", "spmm", "spmm_apply", "vpu", "spmm_vpu", "spmm_vpu",
      "pallas_call")),
    ("jit(agnn_train_step)/transpose(jvp(spmm))/revalue/jit(_take)/gather",
     ("agnn_train_step", "spmm", "revalue", "_take", "gather")),
    ("jit(s)/transpose(jvp(edge_softmax))/jvp(edge_softmax)/mul",
     ("s", "edge_softmax", "edge_softmax", "mul")),
    ("jit(s)/transpose(jvp())/dot_general", ("s", "dot_general")),
    ("", ()),
])
def test_scope_path_strips_transform_wrappers(op_name, path):
    assert scopes.scope_path(op_name) == path


@pytest.mark.parametrize("op_name, inside, outside", [
    ("jit(s)/transpose(jvp(spmm))/jit(spmm_apply)/vpu/jit(spmm_vpu)/x",
     ["spmm", "spmm/vpu"], ["sddmm", "spmm/mxu", "vpu/spmm"]),
    ("jit(s)/jvp(sddmm)/jit(sddmm_apply)/combine/scatter-add",
     ["sddmm", "sddmm/combine"], ["spmm", "sddmm/vpu"]),
    ("jit(s)/jvp(spmm)/jit(spmm_apply)/vpu/jit(spmm_vpu)/spmm_vpu",
     ["spmm/vpu"], ["spmm_vpu/vpu"]),
])
def test_in_scope_matches_components_in_order(op_name, inside, outside):
    path = scopes.scope_path(op_name)
    assert all(scopes.in_scope(path, s) for s in inside)
    assert not any(scopes.in_scope(path, s) for s in outside)


def test_line_key_names_each_op_once():
    key = lambda n: scopes.line_key(scopes.scope_path(n))  # noqa: E731
    assert key("jit(s)/jvp(spmm)/revalue/gather") == "spmm/revalue"
    assert key("jit(s)/jvp(spmm)/jit(spmm_apply)/combine/x") == "spmm/combine"
    assert key("jit(s)/jvp(spmm)/jit(_take)/gather") == "spmm/other"
    assert key("jit(s)/jvp(sddmm)/jit(sddmm_apply)/mxu/x") == "sddmm/mxu"
    assert key("jit(s)/jvp(edge_softmax)/exp") == "edge_softmax"
    assert key("jit(s)/jvp()/dot_general") == "other"
    assert key("") == "other"


def test_hlo_op_names_reads_compiled_text():
    text = ('  %fusion.26 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, '
            'calls=%fc, metadata={op_name="jit(s)/jvp(spmm)/revalue/gather" '
            'source_file="x.py"}\n'
            '  ROOT %spmm_vpu.3 = f32[8]{0} custom-call(%a), '
            'metadata={op_name="jit(s)/jvp(spmm)/vpu/pallas_call"}\n'
            '  %p = f32[8]{0} parameter(0)\n')
    assert scopes.hlo_op_names(text) == {
        "fusion.26": "jit(s)/jvp(spmm)/revalue/gather",
        "spmm_vpu.3": "jit(s)/jvp(spmm)/vpu/pallas_call"}


def _hand_trace():
    # Window 100..300 ns, one step. A VPU kernel 100..150 under spmm/vpu,
    # its combine 140..170 (overlapping the kernel by 10), an SDDMM
    # kernel 180..200, a softmax op 200..220, an unscoped dense op
    # 230..260 and an spmm op past the window's end 290..330.
    ops = [["spmm_vpu.1 f32[8]", 100, 50, 1],
           ["fusion.1 f32[8]", 140, 30, 0],
           ["sddmm_vpu.1 f32[8]", 180, 20, 1],
           ["fusion.2 f32[8]", 200, 20, 0],
           ["fusion.3 f32[8]", 230, 30, 0],
           ["fusion.4 f32[8]", 290, 40, 0]]
    names = ["jit(s)/jvp(spmm)/jit(spmm_apply)/vpu/jit(spmm_vpu)/pallas_call",
             "jit(s)/jvp(spmm)/jit(spmm_apply)/combine/scatter-add",
             "jit(s)/transpose(jvp(sddmm))/jit(sddmm_apply)/vpu/pallas_call",
             "jit(s)/jvp(edge_softmax)/exp",
             "jit(s)/jvp()/dot_general",
             "jit(s)/transpose(jvp(spmm))/revalue/gather"]
    return {"devices": {"/device:TPU:0": ops},
            "op_names": {"/device:TPU:0": names},
            "host": [["bench.window", 100, 200]], "spans": []}


def test_reduce_scopes_by_hand():
    trace = _hand_trace()
    r = scopes.reduce(trace, steps=1)
    ms = {k: v * 1e6 for k, v in r["scope_ms"].items()}    # ns
    assert ms["spmm"] == pytest.approx(70 + 10)     # 100..170, 290..300
    assert ms["spmm/vpu"] == pytest.approx(50)
    assert ms["sddmm"] == pytest.approx(20)
    assert ms["edge_softmax"] == pytest.approx(20)
    line = {k: v * 1e6 for k, v in r["line_ms"].items()}
    assert line["spmm/vpu"] == pytest.approx(50)
    assert line["spmm/combine"] == pytest.approx(30)
    assert line["spmm/revalue"] == pytest.approx(10)
    assert line["other"] == pytest.approx(30)
    assert r["pallas_scoped_ms"] * 1e6 == pytest.approx(70)
    assert r["unnamed"] == 0
    # The overlap of kernel and combine counts once in busy time, twice
    # in the line: the check allows 1%.
    base = tracing.reduce(trace, steps=1)
    assert base["busy_s"] * 1e9 == pytest.approx(150)
    assert sum(line.values()) == pytest.approx(160)


def test_reduce_leaves_tracing_numbers_alone():
    trace = _hand_trace()
    plain = {k: trace[k] for k in ("devices", "host")}
    assert tracing.reduce(trace, 1) == tracing.reduce(plain, 1)


def test_sparse_calls_follow_the_layer_equations():
    dims_gcn, dims_agnn = [128, 256, 256, 40], [128, 256, 256, 256, 40]
    gcn = scopes.gcn_calls(100, 700, dims_gcn)
    assert [c[4] for c in gcn] == [256, 256, 256, 256, 40, 40]
    assert {c[0] for c in gcn} == {"spmm"}
    agnn = scopes.agnn_calls(100, 700, dims_agnn)
    spmm = [c[4] for c in agnn if c[0] == "spmm"]
    assert len(spmm) == 13 and spmm.count(128) == 1
    assert len([c for c in agnn if c[0] == "sddmm"]) == 8
    # The same sparse products as the FLOP counts.
    layers = list(zip(dims_agnn[:-1], dims_agnn[1:]))
    dense = sum(counts.dense(100, a, b) for a, b in layers)
    assert counts.agnn_step(100, 700, dims_agnn) \
        == sum(2 * c[1] * c[4] for c in agnn) + 3 * dense
    layers = list(zip(dims_gcn[:-1], dims_gcn[1:]))
    dense = sum(counts.dense(100, a, b) for a, b in layers)
    assert counts.gcn_step(100, 700, dims_gcn) \
        == sum(2 * c[1] * c[4] for c in gcn) + 3 * dense \
        - counts.dense(100, *layers[0])


def _tree(sddmm_leg=True):
    def node(name, dur, attrs=None, children=()):
        return {"name": name, "start_s": 0.0, "dur_s": dur,
                "attrs": attrs or {}, "events": [],
                "children": list(children)}

    pre = {"vpu_nnz": 40, "vpu_slots": 100, "vpu_segments": 25, "cs": 4}
    legs = [node("graphops.leg", 2.0, {"leg": "spmm"},
                 [node("preprocess.spmm", 1.5, pre)]),
            node("graphops.leg", 2.0, {"leg": "spmm_t"},
                 [node("preprocess.spmm", 1.5, dict(pre, vpu_nnz=60))])]
    if sddmm_leg:
        legs.append(node("graphops.leg", 15.5, {"leg": "sddmm"},
                         [node("preprocess.sddmm", 15.0,
                               {"vpu_nnz": 1, "vpu_slots": 1000})]))
    return [node("graphops.build", 20.0, {},
                 [node("graphops.transpose", 0.2)] + legs
                 + [node("graphops.edges", 0.1)])]


def test_plan_span_readers():
    tree = _tree()
    assert scopes.vpu_slot_fill(tree) == pytest.approx(50.0)
    assert scopes.plan_sddmm_s(tree) == 15.5
    rep = scopes.build_report(tree)
    assert rep["self_s"] == pytest.approx(20.0 - 0.2 - 19.5 - 0.1)
    assert scopes.plan_sddmm_s(_tree(sddmm_leg=False)) == 0.0


def test_plan_span_readers_find_nothing_in_an_older_program():
    tree = [{"name": "preprocess.spmm", "start_s": 0.0, "dur_s": 1.0,
             "attrs": {"nnz": 5}, "events": [], "children": []}]
    assert scopes.vpu_slot_fill(tree) is None
    assert scopes.plan_sddmm_s(tree) is None
    assert scopes.build_report(tree) is None


def test_readings_are_absent_where_the_scope_is():
    scoped = {"scope_ms": {"spmm": 0.0, "spmm/vpu": 0.0, "sddmm": 0.0,
                           "edge_softmax": 0.0}}
    calls = scopes.gcn_calls(100, 700, [8, 16])
    assert scopes.readings(scoped, [], calls, PEAKS) == {}


@pytest.mark.parametrize("slack", [1.0, 1.5, 1000.0])
def test_roofline_at_most_100_percent(slack):
    calls = scopes.agnn_calls(169_343, 2_501_785, [128, 256, 256, 256, 40])
    read = {}
    for op in ("spmm", "sddmm"):
        least, bound = scopes.least_seconds(calls, op, PEAKS)
        assert bound == "memory"
        scoped = {"scope_ms": {"spmm": 0.0, "spmm/vpu": 0.0, "sddmm": 0.0,
                               "edge_softmax": 0.0}}
        scoped["scope_ms"][op] = least * 1e3 * slack
        read.update(scopes.readings(scoped, [], calls, PEAKS))
    for op in ("spmm", "sddmm"):
        assert 0.0 < read[f"{op}_roofline"] <= 100.0 + 1e-9
        assert read[f"{op}_roofline"] == pytest.approx(100.0 / slack)


@pytest.mark.parametrize("workload", ["gcn.arxiv", "agnn.arxiv"])
def test_probe_runs_a_tiny_cell(workload, tmp_path):
    """The probe end to end on the CPU (no device plane in the profile):
    the plan spans' readings are there, the device ones are not."""
    from bench.tests.test_harness import tiny_cell

    out = scopes.probe_cell(tiny_cell(workload), 2**31 + 7, 0.0, PEAKS,
                            save=None)
    build = out["plan"]["build"]
    assert build["self_s"] >= 0.0 and len(build["children_s"]) == 6
    read = out["readings"]
    assert 0.0 < read["vpu_slot_fill"] <= 100.0
    assert read["plan_sddmm_s"] > 0.0
    assert not any(k.endswith(("_ms_per_step", "_roofline")) for k in read)
    assert out["window"]["steps"] >= 2


def test_extract_collects_program_spans_from_the_host_plane(tmp_path):
    import jax

    from repro.obs.trace import Tracer

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with Tracer().span("graphops.build"):
                jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    trace = scopes.extract(str(path), {})
    assert [s[0] for s in trace["spans"]] == ["graphops.build"]
    assert [h[0] for h in trace["host"]] == ["bench.window"]
    (w,), (s,) = trace["host"], trace["spans"]
    assert w[1] <= s[1] and s[1] + s[2] <= w[1] + w[2]


def test_recorded_trace():
    # The first 400 ops of an agnn.arxiv window (the forward pass's
    # SDDMMs, edge softmaxes and SpMMs), traced on one TPU v5e and cut
    # down by bench/scopes.py --save.
    trace = json.loads((DATA / "scoped_trace.json").read_text())
    plain = {k: trace[k] for k in ("devices", "host")}
    base = tracing.reduce(trace, steps=1)
    assert base == tracing.reduce(plain, steps=1)
    r = scopes.reduce(trace, steps=1)
    for scope in scopes.SCOPES:
        assert r["scope_ms"][scope] > 0.0, scope
    assert 0.0 < r["scope_ms"]["spmm/vpu"] < r["scope_ms"]["spmm"]
    busy, pallas = 1e3 * base["busy_s"], 1e3 * base["pallas_s"]
    assert r["pallas_scoped_ms"] == pytest.approx(pallas, rel=0.01)
    assert sum(r["line_ms"].values()) == pytest.approx(busy, rel=0.01)
    # Ops without an op_name are the compiler's (async copies and
    # slices): a sliver of the busy time, counted under "other".
    dev = next(iter(trace["devices"]))
    unnamed = [op for op, name in zip(trace["devices"][dev],
                                      trace["op_names"][dev]) if not name]
    assert r["unnamed"] == len(unnamed) > 0
    assert sum(op[2] for op in unnamed) * 1e-6 < 0.01 * busy
