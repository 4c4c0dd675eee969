"""Autotuning subsystem (`repro.tune`): VMEM model stays within budget,
search is deterministic under a stubbed timer, the persistent cache
round-trips and invalidates on signature change, and tuned configs are
numerically transparent (bit-identical outputs on exactly-representable
data — tuning reassociates sums, so bit-identity is asserted with
integer-valued operands where float addition is exact)."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.common import corpus
from repro.api import ExecSpec
from repro.core import preprocess
from repro.core.formats import WINDOW
from repro.core.sddmm import LibraSDDMM
from repro.core.spmm import LibraSpMM
from repro.sparse.generate import banded_csr, mixed_csr, power_law_csr
from repro.sparse.matrix import coo_to_csr
from repro.tune import (
    DEFAULT_TUNE,
    PlanCache,
    TuneConfig,
    VMEM_BUDGET_BYTES,
    matrix_features,
    matrix_signature,
    model_tune_sddmm,
    model_tune_spmm,
    occupancy_report,
    search_spmm,
    spmm_candidates,
    tune_key,
    tune_spmm,
    vmem_sddmm_bytes,
    vmem_spmm_bytes,
)
from repro.tune.model import (lane_tile, spmm_mxu_step_bytes,
                              spmm_vpu_step_bytes)


def _sparse(m, k, nnz, seed=0):
    rng = np.random.default_rng(seed)
    flat = rng.choice(m * k, size=min(nnz, m * k), replace=False)
    vals = rng.standard_normal(flat.size).astype(np.float32)
    return coo_to_csr(m, k, (flat // k).astype(np.int32),
                      (flat % k).astype(np.int32), vals)


def _int_valued(a):
    """Same pattern, small-integer values: float addition is exact, so
    any reassociation (different nt/threshold/grid order) must be
    bit-identical."""
    rng = np.random.default_rng(7)
    data = rng.integers(1, 4, a.nnz).astype(np.float32)
    return coo_to_csr(a.m, a.k, *a.to_coo()[:2], data)


# ------------------------------------------------------------- model ---
def test_model_within_budget_for_every_benchmark_matrix():
    """Acceptance: tune="model" sizes nt (and kf_tile) inside the
    stated VMEM budget for the whole benchmark corpus."""
    for name, a in corpus().items():
        cfg = model_tune_spmm(a)
        step = vmem_spmm_bytes(cfg, bk=cfg.bk, ts=cfg.ts_tile)
        assert step <= VMEM_BUDGET_BYTES, (name, cfg, step)
        assert occupancy_report(step)["fits"]
        cfg_sd = model_tune_sddmm(a)
        step_sd = vmem_sddmm_bytes(cfg_sd, bk=cfg_sd.bk, ts=cfg_sd.ts_tile)
        assert step_sd <= VMEM_BUDGET_BYTES, (name, cfg_sd, step_sd)


@pytest.mark.parametrize("m,k,nnz,n", [
    (16, 1_000_000, 50, 128),    # huge k: no footprint grows with k
    (8, 8, 1, 4096),             # huge n: nt stays a lane multiple
    (4096, 4096, 2000, 512),     # big both ways
    (61, 93, 37, 37),            # nothing aligned
])
def test_model_spmm_budget_adversarial(m, k, nnz, n):
    a = _sparse(m, k, nnz, seed=m + k)
    cfg = model_tune_spmm(a, n=n)
    step = vmem_spmm_bytes(cfg, bk=cfg.bk, ts=cfg.ts_tile)
    assert step <= VMEM_BUDGET_BYTES, (cfg, step)
    assert cfg.nt % 128 == 0


@pytest.mark.parametrize("m,k,nnz,kf", [
    (64, 500_000, 100, 128),     # huge kcols: no footprint grows with it
    (64, 64, 200, 8192),         # huge feature dim: kf_tile bounds it
    (8192, 1024, 3000, 256),     # tall X (the documented residual term)
])
def test_model_sddmm_budget_adversarial(m, k, nnz, kf):
    a = _sparse(m, k, nnz, seed=m + k + kf)
    cfg = model_tune_sddmm(a, kf=kf)
    step = vmem_sddmm_bytes(cfg, bk=cfg.bk, ts=cfg.ts_tile)
    assert step <= VMEM_BUDGET_BYTES, (cfg, step)


def test_matrix_features_histogram():
    a = banded_csr(64, 64, 8, 1.0, seed=1)
    feat = matrix_features(a)
    assert feat.nnz == a.nnz
    # Histogram conserves nnz and vector counts.
    counts = np.arange(9)
    assert int((feat.win_vec_hist * counts[None, :]).sum()) == a.nnz
    assert feat.nnz_at_least(1) == a.nnz
    assert feat.nnz_at_least(9) == 0
    assert 0.0 < feat.window_density <= 1.0


def test_model_respects_explicit_threshold_and_modes():
    a = mixed_csr(96, 96, seed=3)
    assert model_tune_spmm(a, threshold=5).threshold == 5
    # Forced modes arrive with a pinned threshold; the model keeps it.
    assert model_tune_spmm(a, mode="tcu", threshold=1).threshold == 1
    op = LibraSpMM(a, mode="vpu")  # tune="model" default
    assert op.plan.meta["tc_ratio"] == 0.0


def test_explicit_bk_ts_tile_reach_tuner_and_plan():
    """The emitted config must describe the plan actually built: explicit
    bk/ts_tile flow through the tuner into both."""
    a = mixed_csr(96, 96, seed=3)
    op = LibraSpMM(a, bk=8, ts_tile=16, tune="model")
    assert op.tune_config.bk == 8 and op.tune_config.ts_tile == 16
    assert op.plan.tc.bk == 8 and op.plan.vpu.ts == 16
    # Without overrides the model sizes ts_tile from the row histogram.
    cfg = model_tune_spmm(a)
    assert cfg.ts_tile in (8, 16, 32)
    assert LibraSpMM(a, tune="model").plan.vpu.ts == cfg.ts_tile


def test_tall_x_streams_inside_budget():
    """Very tall X used to be un-fittable (the VPU kernel kept full X
    feature tiles resident); X now stays in HBM and a step fetches only
    the rows its ids name, so the model fits without warning."""
    import warnings as _warnings

    a = _sparse(50_000, 64, 200, seed=1)
    with _warnings.catch_warnings():
        _warnings.simplefilter("error", RuntimeWarning)
        cfg = model_tune_sddmm(a, kf=128)
    step = vmem_sddmm_bytes(cfg, bk=cfg.bk, ts=cfg.ts_tile)
    assert step <= VMEM_BUDGET_BYTES


def test_model_warns_on_pathological_overrides():
    """Explicit plan parameters can still make every tile candidate
    over-budget (a huge VPU tile is resident regardless of panel
    sizes); the model must warn instead of silently emitting it."""
    a = _sparse(64, 64, 100, seed=2)
    with pytest.warns(RuntimeWarning, match="VMEM budget"):
        model_tune_sddmm(a, kf=128, ts_tile=2**20)


# ------------------------------------------------------------ search ---
def _seq_timer(seq):
    """Deterministic stub: returns seq values in candidate order (repeats
    the list on later searches) and counts invocations."""
    state = {"i": 0}

    def timer(fn):
        fn()  # still exercise the real apply path once
        v = seq[state["i"] % len(seq)]
        state["i"] += 1
        return float(v)

    timer.state = state
    return timer


def test_search_is_deterministic_given_fixed_timer():
    a = mixed_csr(64, 64, seed=4)
    ncand = len(spmm_candidates(a, n=32, mode="hybrid", threshold=None))
    assert ncand >= 2
    seq = [9.0] * ncand
    seq[1] = 1.0  # candidate #1 (the model pick) is cheapest
    cfg1, t1 = search_spmm(a, n=32, timer=_seq_timer(seq))
    cfg2, t2 = search_spmm(a, n=32, timer=_seq_timer(seq))
    assert cfg1 == cfg2
    assert t1 == t2
    model = model_tune_spmm(a, n=32)
    assert cfg1 == model.replace(source="search")


def test_search_never_loses_to_default_on_ties():
    """Candidate #0 is the floor search can't lose to (on the XLA timing
    backend: the default *threshold* — tile fields are inert there) and
    ties resolve to it, so search can never pick a config that timed
    worse than the hardcoded defaults."""
    a = mixed_csr(64, 64, seed=4)
    ncand = len(spmm_candidates(a, n=32, mode="hybrid", threshold=None))
    cfg, timings = search_spmm(a, n=32, timer=_seq_timer([5.0] * ncand))
    assert cfg.threshold == preprocess.DEFAULT_SPMM_THRESHOLD
    assert timings[0] == min(timings.values())
    # On the pallas backend candidate #0 is the default config under the
    # model's lane-tile cap, and segment-cap candidates join the grid.
    pallas_cands = spmm_candidates(a, n=32, mode="hybrid", threshold=None,
                                   backend="pallas")
    assert pallas_cands[0] == DEFAULT_TUNE.replace(
        threshold=preprocess.DEFAULT_SPMM_THRESHOLD,
        nt=model_tune_spmm(a, n=32).nt)
    assert len(pallas_cands) > len(
        spmm_candidates(a, n=32, mode="hybrid", threshold=None))


# ------------------------------------------------------------- cache ---
def test_cache_roundtrip_and_signature_invalidation(tmp_path):
    a = mixed_csr(64, 64, seed=5)
    pc = PlanCache(str(tmp_path))
    key = tune_key(a, op="spmm", width=128, dtype="float32", backend="xla",
                   mode="hybrid", tune="search")
    assert pc.get(key) is None
    cfg = TuneConfig(nt=256, threshold=4, source="search")
    pc.put(key, cfg)
    got = pc.get(key)
    assert got == cfg.replace(source="cache")

    # One extra non-zero ⇒ different sparsity signature ⇒ different key.
    rows, cols, vals = a.to_coo()
    free = next((r, c) for r in range(a.m) for c in range(a.k)
                if not ((rows == r) & (cols == c)).any())
    a2 = coo_to_csr(a.m, a.k, np.append(rows, free[0]).astype(np.int32),
                    np.append(cols, free[1]).astype(np.int32),
                    np.append(vals, 1.0).astype(np.float32))
    assert matrix_signature(a2) != matrix_signature(a)
    key2 = tune_key(a2, op="spmm", width=128, dtype="float32",
                    backend="xla", mode="hybrid", tune="search")
    assert key2 != key and pc.get(key2) is None

    # Same pattern, different values ⇒ same signature (pattern-keyed).
    a3 = coo_to_csr(a.m, a.k, rows, cols,
                    (vals + 1.0).astype(np.float32))
    assert matrix_signature(a3) == matrix_signature(a)

    # Version drift and corruption are treated as misses.
    doc = json.load(open(pc._path(key)))
    doc["version"] = 999
    json.dump(doc, open(pc._path(key), "w"))
    assert pc.get(key) is None
    with open(pc._path(key), "w") as f:
        f.write("{not json")
    assert pc.get(key) is None


def test_second_construction_hits_persistent_cache(tmp_path):
    """Acceptance: re-constructing the same operator re-uses the cached
    search result — zero timer invocations the second time."""
    a = mixed_csr(64, 64, seed=6)
    pc = PlanCache(str(tmp_path))
    ncand = len(spmm_candidates(a, n=128, mode="hybrid", threshold=None))
    timer = _seq_timer(list(range(1, ncand + 1)))
    cfg1 = tune_spmm(a, tune="search", cache=pc, timer=timer)
    assert timer.state["i"] == ncand
    cfg2 = tune_spmm(a, tune="search", cache=pc, timer=timer)
    assert timer.state["i"] == ncand  # no re-search
    assert cfg2.source == "cache"
    assert cfg2.replace(source="x") == cfg1.replace(source="x")
    # The whole-operator path takes the same cache hit.
    op = LibraSpMM(a, tune="search", tune_cache=pc)
    assert op.tune_config.source == "cache"
    assert len(os.listdir(tmp_path)) == 1


def test_cache_default_dir_env_override(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE_DIR", str(tmp_path / "env"))
    pc = PlanCache()
    pc.put("k", TuneConfig())
    assert (tmp_path / "env" / "k.json").exists()


def test_cache_size_cap_evicts_lru(tmp_path, monkeypatch):
    import time as _time

    pc = PlanCache(str(tmp_path), max_entries=3)
    for i in range(6):
        pc.put(f"k{i}", TuneConfig(nt=128 * (i + 1)))
        _time.sleep(0.01)   # distinct mtimes on coarse filesystems
    assert pc.size() == 3
    assert pc.get("k0") is None and pc.get("k1") is None
    assert pc.get("k5").nt == 768
    # a hit refreshes recency: k3 survives the next eviction, k4 goes
    _time.sleep(0.01)
    assert pc.get("k3") is not None
    _time.sleep(0.01)
    pc.put("k6", TuneConfig(nt=256))
    assert pc.get("k3") is not None and pc.get("k4") is None
    # env override for the default cap
    monkeypatch.setenv("REPRO_TUNE_CACHE_MAX", "7")
    assert PlanCache(str(tmp_path)).max_entries == 7


def test_cache_concurrent_writers_same_key(tmp_path):
    """Atomic rename keeps racing writers safe: no torn entries, no
    errors, and the surviving entry is always parseable."""
    import threading

    pc = PlanCache(str(tmp_path), max_entries=8)
    errors = []

    def writer(i):
        try:
            for j in range(25):
                pc.put("shared", TuneConfig(nt=128 * (1 + (i + j) % 4)))
                got = pc.get("shared")
                assert got is None or got.source == "cache"
        except Exception as e:  # pragma: no cover - failure path
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    got = pc.get("shared")
    assert got is not None and got.nt in (128, 256, 384, 512)
    assert pc.size() == 1


# ------------------------------------------------- numerics / outputs ---
def test_tuned_configs_bit_identical_outputs_spmm(rng):
    a = _int_valued(power_law_csr(96, 80, 7.0, seed=8))
    b = jnp.asarray(rng.integers(-2, 3, (a.k, 160)).astype(np.float32))
    ref_out = None
    configs = ["off", "model",
               TuneConfig(nt=128, threshold=2),   # two lane tiles
               TuneConfig(nt=256)]                # one lane tile
    for tune in configs:
        op = LibraSpMM(a, tune=tune)
        for backend in ("xla", "pallas"):
            out = np.asarray(op(b, backend=backend))
            if ref_out is None:
                ref_out = out
            assert np.array_equal(out, ref_out), (tune, backend)


def test_tuned_configs_bit_identical_outputs_sddmm(rng):
    a = _int_valued(mixed_csr(72, 88, seed=9))
    x = jnp.asarray(rng.integers(-2, 3, (a.m, 64)).astype(np.float32))
    y = jnp.asarray(rng.integers(-2, 3, (a.k, 64)).astype(np.float32))
    ref_out = None
    for tune in ("off", "model", TuneConfig(kf_tile=128),
                 TuneConfig(threshold=8),
                 TuneConfig(threshold=1),          # everything on the MXU
                 TuneConfig(threshold=8, ts=0, cs=0)):  # per-block launch

        op = LibraSDDMM(a, tune=tune)
        for backend in ("xla", "pallas"):
            out = np.asarray(op(x, y, backend=backend))
            if ref_out is None:
                ref_out = out
            assert np.array_equal(out, ref_out), (tune, backend)


def test_block_outer_downgrade_on_shared_ranks(rng):
    """Multi-block windows on the per-block launch: every grid step owns
    its output block, so block_outer stays legal (no downgrade) and the
    combine sums the blocks that share a window."""
    a = banded_csr(64, 256, 48, 1.0, seed=10)  # 48 vecs/window > bk=32
    # Two 128-lane tiles of the 256-wide B: the apply runs block_outer.
    op = LibraSpMM(a, tune=TuneConfig(nt=128, ts=0, cs=0))
    assert op.plan.tc.nblk > op.plan.tc.n_active
    b = rng.standard_normal((a.k, 256)).astype(np.float32)
    out = np.asarray(op(jnp.asarray(b), backend="pallas"))
    np.testing.assert_allclose(out, a.to_dense() @ b, rtol=1e-3, atol=1e-3)


def test_sddmm_huge_kcols_streams_y(rng):
    """kcols in the thousands (not a tile multiple): the id-driven row
    fetch must reach every column, the last included."""
    a = _sparse(40, 5000, 300, seed=11)
    x = rng.standard_normal((a.m, 32)).astype(np.float32)
    y = rng.standard_normal((a.k, 32)).astype(np.float32)
    from repro.kernels import ref

    oracle = np.asarray(ref.sddmm_dense_oracle(a.to_dense(), x, y))
    op = LibraSDDMM(a, tune="model")
    out = np.asarray(op(jnp.asarray(x), jnp.asarray(y), backend="pallas"))
    np.testing.assert_allclose(out, oracle, rtol=1e-3, atol=1e-3)


def test_tune_off_reproduces_legacy_defaults():
    a = mixed_csr(64, 64, seed=12)
    op = LibraSpMM(a, tune="off")
    assert op.plan.threshold == preprocess.DEFAULT_SPMM_THRESHOLD
    assert op.plan.tc.bk == preprocess.DEFAULT_BK_SPMM
    assert op.tune_config.nt == 128
    with pytest.raises(ValueError):
        LibraSpMM(a, tune="bogus")


# ------------------------------------------------------ per-call tile ---
_TILE_FIELD = {"spmm": "nt", "sddmm": "kf_tile"}


@pytest.mark.parametrize("width,tile,tile_256", [
    (40, 128, 128), (128, 128, 128), (160, 256, 256), (256, 256, 256),
    (384, 512, 256), (640, 512, 256)])
@pytest.mark.parametrize("op", ["spmm", "sddmm"])
def test_call_tile_follows_the_width(op, width, tile, tile_256):
    """A call takes the tile under the plan's cap that covers its width
    in the fewest tiles, the narrowest of those: 384 lanes take one
    512-lane tile, or two of 256 under a cap of 256, not three of 128;
    under a cap of 128 every width keeps 128."""
    field = _TILE_FIELD[op]
    assert lane_tile(op, width, TuneConfig(**{field: 512})) == tile
    assert lane_tile(op, width, TuneConfig(**{field: 256})) == tile_256
    assert lane_tile(op, width, TuneConfig(**{field: 128})) == 128


@pytest.mark.parametrize("op", ["spmm", "sddmm"])
def test_call_tile_keeps_a_narrower_tile_over_the_budget(op):
    """A plan whose step at the wider tile exceeds the VMEM budget keeps
    the narrower tile: segments of 512 VPU elements are charged 16 MiB
    of rows a step at 512 lanes (SpMM: the rows and their relayout;
    SDDMM: two operands' rows), 8 MiB at 256."""
    field = _TILE_FIELD[op]
    vmem = vmem_spmm_bytes if op == "spmm" else vmem_sddmm_bytes
    cfg = TuneConfig(**{field: 512}, ts_tile=32, cs=512)
    bk = preprocess.DEFAULT_BK_SPMM if op == "spmm" \
        else preprocess.DEFAULT_BK_SDDMM
    assert vmem(cfg, bk=bk, ts=32) > VMEM_BUDGET_BYTES
    tile = lane_tile(op, 512, cfg)
    assert tile == 256
    assert vmem(cfg.replace(**{field: tile}), bk=bk, ts=32) \
        <= VMEM_BUDGET_BYTES


def test_call_tile_prices_the_heads():
    """``spmm_mxu`` stacks a copy of the fetched rows a head: 8 heads of
    64 over segments of 32 blocks of 32 vectors (1,024 rows) need
    (1 + 8) · 1,024 · 512 · 4 B at 512 lanes, over the budget, so the
    call keeps 256 lanes; one head fits 512."""
    cfg = TuneConfig(nt=512, bk=32, ts=32, cs=32, ts_tile=32)
    assert vmem_spmm_bytes(cfg, bk=32, ts=32, heads=8) > VMEM_BUDGET_BYTES
    assert lane_tile("spmm", 512, cfg, heads=8) == 256
    assert lane_tile("spmm", 512, cfg) == 512


def test_model_caps_tiles_by_the_budget_not_the_tuned_width():
    """``n`` / ``kf`` price the threshold only: the caps are the widest
    tiles whose step fits the budget, and a budget under the widest
    step narrows them."""
    a = power_law_csr(256, 256, 6.0, seed=5)
    spmm = model_tune_spmm(a, n=128)
    sddmm = model_tune_sddmm(a, kf=128)
    assert (spmm.nt, sddmm.kf_tile) == (512, 512)
    over = vmem_spmm_bytes(spmm, bk=spmm.bk, ts=spmm.ts_tile) - 1
    assert model_tune_spmm(a, n=128, budget=over).nt == 256
    over = vmem_sddmm_bytes(sddmm, bk=sddmm.bk, ts=sddmm.ts_tile) - 1
    assert model_tune_sddmm(a, kf=128, budget=over).kf_tile == 256


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_search_candidates_keep_the_model_caps(backend):
    """The lane-tile cap is a VMEM bound, not a timed choice: every
    search candidate, the default plan at #0 included, carries the
    model's cap, so a search result keeps it."""
    from repro.tune.search import sddmm_candidates

    a = power_law_csr(96, 96, 8.0, seed=4)
    nt = model_tune_spmm(a, n=128).nt
    kf = model_tune_sddmm(a, kf=128).kf_tile
    assert {c.nt for c in spmm_candidates(
        a, n=128, mode="hybrid", threshold=None, backend=backend)} == {nt}
    assert {c.kf_tile for c in sddmm_candidates(
        a, kf=128, mode="hybrid", threshold=None, backend=backend)} == {kf}
    ncand = len(spmm_candidates(a, n=128, mode="hybrid", threshold=None,
                                backend=backend))
    cfg, _ = search_spmm(a, n=128, backend=backend,
                         timer=_seq_timer([5.0] * ncand))
    assert cfg.nt == nt


def test_tune_off_keeps_the_128_lane_tile():
    """``tune="off"`` keeps ``DEFAULT_TUNE``'s caps of 128: every call
    runs the 128-lane kernels, whatever its width."""
    a = mixed_csr(64, 64, seed=12)
    for op, cls in (("spmm", LibraSpMM), ("sddmm", LibraSDDMM)):
        cfg = cls(a, spec=ExecSpec(tune="off")).tune_config
        for width in (40, 256, 512):
            assert lane_tile(op, width, cfg) == 128


def test_vmem_model_charges_mosaic_tiles():
    """Pipelined blocks are charged in whole (8, 128) tiles, so an
    8-wide value block costs a 128-wide one; fetched rows one sublane
    per row, as Mosaic lays the scratch out, plus the SpMM kernels'
    relayout of them (one copy a head for ``spmm_mxu``'s stacked dot
    operand). tests/test_tpu_compile.py holds the model to compiles."""
    rows = lambda els, nt: 2 * els * WINDOW * nt * 4  # noqa: E731
    assert spmm_vpu_step_bytes(8, 256) - rows(8, 256) == \
        spmm_vpu_step_bytes(128, 256) - rows(128, 256)
    assert spmm_mxu_step_bytes(1024, 512) - spmm_mxu_step_bytes(512, 512) \
        == 2 * 512 * 512 * 4 + 2 * WINDOW * 512 * 4
    assert spmm_mxu_step_bytes(32, 256, heads=4) \
        - spmm_mxu_step_bytes(32, 256) == 3 * 32 * 256 * 4
