"""Compile-only checks of the Pallas applies for a described TPU v5e.

Nothing runs: each test lowers ``spmm_apply`` / ``sddmm_apply`` with
``backend="pallas", interpret=False`` against shapes placed on one chip
of a ``v5e:2x2`` topology that is described, not attached, and lets the
TPU compiler accept or refuse it — the tiling, VMEM and memory rules
the Pallas interpreter cannot check. The tuned §4.3 segment tables and
tables of one block (tile) per segment run at real widths (n = 256,
kf = 128) with ``k`` in the tens of thousands, so B, X and Y are
fetched row by row from HBM.

The topology is described inside a module fixture (never at import):
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.api import ExecSpec
from repro.core.sddmm import LibraSDDMM
from repro.core.spmm import LibraSpMM
from repro.kernels.ops import sddmm_apply, spmm_apply
from repro.sparse.generate import block_graph, power_law_graph

N, KF = 256, 128
NODES, EDGES = 20_000, 140_000
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described-chip compile is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def graphs():
    return {"powerlaw": power_law_graph(NODES, EDGES, seed=0),
            "block": block_graph(NODES, EDGES, seed=1)}


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        dict(tree))


def _check(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used


def _one_unit(op, cls, a, spec):
    """``op``'s plan rebuilt at one block (``ts=1``) and one tile
    (``cs=ts_tile``) per segment."""
    cfg = op.tune_config
    return cls(a, spec=dataclasses.replace(
        spec, tune=cfg.replace(ts=1, cs=cfg.ts_tile)))


@pytest.mark.parametrize("one_unit", [False, True],
                         ids=["segmented", "one_unit"])
@pytest.mark.parametrize("graph", ["powerlaw", "block"])
def test_spmm_apply_compiles_for_v5e(one_chip, graphs, graph, one_unit):
    a = graphs[graph]
    spec = ExecSpec(backend="pallas", tune="model", tune_n=N)
    op = LibraSpMM(a, spec=spec)
    if one_unit:
        op = _one_unit(op, LibraSpMM, a, spec)
        assert op.plan.meta["tc_segments"].nseg == op.plan.tc.nblk
    arrs = op.arrays.apply_arrays()
    b = jax.ShapeDtypeStruct((a.k, N), jnp.float32, sharding=one_chip)
    _check(spmm_apply.lower(_shapes(arrs, one_chip), b, m=op.m,
                            nwin=op.nwin, backend="pallas",
                            cfg=op.tune_config, interpret=False).compile())


@pytest.mark.parametrize("one_unit", [False, True],
                         ids=["segmented", "one_unit"])
@pytest.mark.parametrize("graph", ["powerlaw", "block"])
def test_sddmm_apply_compiles_for_v5e(one_chip, graphs, graph, one_unit):
    a = graphs[graph]
    spec = ExecSpec(backend="pallas", tune="model", tune_kf=KF)
    op = LibraSDDMM(a, spec=spec)
    if one_unit:
        op = _one_unit(op, LibraSDDMM, a, spec)
        assert op.plan.meta["seg_spt"] == 1
    arrs = op.arrays.apply_arrays()
    x = jax.ShapeDtypeStruct((a.m, KF), jnp.float32, sharding=one_chip)
    y = jax.ShapeDtypeStruct((a.k, KF), jnp.float32, sharding=one_chip)
    _check(sddmm_apply.lower(_shapes(arrs, one_chip), x, y, nnz=op.nnz,
                             backend="pallas", cfg=op.tune_config,
                             interpret=False).compile())


def _kernel_scopes(text: str) -> dict[str, str]:
    """Each Pallas custom call's instruction name → its ``op_name``."""
    out = {}
    for line in text.splitlines():
        if "tpu_custom_call" not in line or " = " not in line:
            continue
        name = line.split(" = ", 1)[0].strip().lstrip("%").removeprefix(
            "ROOT ").lstrip("%")
        m = re.search(r'op_name="([^"]*)"', line)
        out[name] = m.group(1) if m else ""
    return out


@pytest.mark.parametrize("kind", ["spmm", "sddmm"])
def test_applies_name_their_kernels_and_scopes(one_chip, graphs, kind):
    """The compiled applies carry the kernel names and the ``mxu`` /
    ``vpu`` / ``combine`` scopes that a profile is split by."""
    a = graphs["powerlaw"]
    if kind == "spmm":
        op = LibraSpMM(a, spec=ExecSpec(backend="pallas", tune="model",
                                        tune_n=N))
        arrs = op.arrays.apply_arrays()
        b = jax.ShapeDtypeStruct((a.k, N), jnp.float32, sharding=one_chip)
        lowered = spmm_apply.lower(_shapes(arrs, one_chip), b, m=op.m,
                                   nwin=op.nwin, backend="pallas",
                                   cfg=op.tune_config, interpret=False)
    else:
        op = LibraSDDMM(a, spec=ExecSpec(backend="pallas", tune="model",
                                         tune_kf=KF))
        arrs = op.arrays.apply_arrays()
        x = jax.ShapeDtypeStruct((a.m, KF), jnp.float32, sharding=one_chip)
        y = jax.ShapeDtypeStruct((a.k, KF), jnp.float32, sharding=one_chip)
        lowered = sddmm_apply.lower(_shapes(arrs, one_chip), x, y,
                                    nnz=op.nnz, backend="pallas",
                                    cfg=op.tune_config, interpret=False)
    text = lowered.compile().as_text()
    kernels = _kernel_scopes(text)
    for stream in ("mxu", "vpu"):
        (op_name,) = [v for k, v in kernels.items()
                      if k.startswith(f"{kind}_{stream}")]
        assert f"/{stream}/" in op_name, op_name
        assert op_name.endswith(f"{kind}_{stream}/pallas_call"), op_name
    assert len(kernels) == 2, kernels
    assert re.search(r'op_name="[^"]*/combine/[^"]*scatter-add', text)


@pytest.mark.parametrize("heads,head_dim", [(4, 64), (4, 40)])
def test_multihead_applies_compile_for_v5e(one_chip, graphs, heads,
                                           head_dim):
    """UniMP's layouts: 4 heads of 64 (256 lanes, two whole heads a lane
    tile) and 4 heads of 40 (160 features padded to 256; head 3
    straddles the first two tiles). Both applies compile with their
    kernels named ``_mh``, inside the ``mxu``/``vpu`` scopes."""
    from repro.kernels import ref

    a = graphs["powerlaw"]
    width = heads * head_dim
    sp = LibraSpMM(a, spec=ExecSpec(backend="pallas", tune="model",
                                    tune_n=N))
    arrs = sp.arrays.apply_arrays(revalue=True)
    ev = jax.ShapeDtypeStruct((a.nnz, heads), jnp.float32,
                              sharding=one_chip)
    b = jax.ShapeDtypeStruct((a.k, width), jnp.float32, sharding=one_chip)

    def spmm(arrs, ev, b):
        return spmm_apply(ref.revalue_spmm_arrays(arrs, ev), b, m=sp.m,
                          nwin=sp.nwin, backend="pallas",
                          cfg=sp.tune_config, interpret=False)

    sd = LibraSDDMM(a, spec=ExecSpec(backend="pallas", tune="model",
                                     tune_kf=KF))
    x = jax.ShapeDtypeStruct((a.m, width), jnp.float32, sharding=one_chip)
    y = jax.ShapeDtypeStruct((a.k, width), jnp.float32, sharding=one_chip)
    compiled = {
        "spmm": jax.jit(spmm).lower(_shapes(arrs, one_chip), ev,
                                    b).compile(),
        "sddmm": sddmm_apply.lower(
            _shapes(sd.arrays.apply_arrays(),
                    one_chip), x, y, nnz=sd.nnz, backend="pallas",
            cfg=sd.tune_config, heads=heads, interpret=False).compile(),
    }
    for kind, exe in compiled.items():
        _check(exe)
        kernels = _kernel_scopes(exe.as_text())
        for stream in ("mxu", "vpu"):
            (op_name,) = [v for k, v in kernels.items()
                          if k.startswith(f"{kind}_{stream}_mh")]
            assert f"/{stream}/" in op_name, op_name
        assert len(kernels) == 2, kernels


def test_sddmm_apply_compiles_at_a_256_feature_tile(one_chip, graphs):
    """A width-256 SDDMM of a plan tuned at the default ``tune_kf``
    takes one 256-feature tile (the cap is the budget's, not
    ``tune_kf``'s) and compiles, kernels named and scoped."""
    from repro.tune.model import lane_tile

    a = graphs["powerlaw"]
    op = LibraSDDMM(a, spec=ExecSpec(backend="pallas", tune="model"))
    assert lane_tile("sddmm", N, op.tune_config) == N
    arrs = op.arrays.apply_arrays()
    x = jax.ShapeDtypeStruct((a.m, N), jnp.float32, sharding=one_chip)
    y = jax.ShapeDtypeStruct((a.k, N), jnp.float32, sharding=one_chip)
    exe = sddmm_apply.lower(_shapes(arrs, one_chip), x, y, nnz=op.nnz,
                            backend="pallas", cfg=op.tune_config,
                            interpret=False).compile()
    _check(exe)
    assert {k.split(".")[0] for k in _kernel_scopes(exe.as_text())} == \
        {"sddmm_mxu", "sddmm_vpu"}


# (kernel, rows a step fetches per operand, lane tile, heads): the arxiv
# plans' steps at 512 lanes, the widest segment caps the tuner emits
# (Ts 32 × bk 32 = 1,024 MXU vectors; Cs 8 × 32 = 256 VPU elements) and
# UniMP's four heads at 256.
VMEM_CASES = [
    ("spmm_mxu", 32, 512, 1), ("spmm_mxu", 1024, 512, 1),
    ("spmm_mxu", 128, 256, 4),
    ("spmm_vpu", 32, 512, 1), ("spmm_vpu", 256, 512, 1),
    ("spmm_vpu", 32, 256, 4),
    ("sddmm_mxu", 128, 512, 1), ("sddmm_mxu", 512, 512, 1),
    ("sddmm_mxu", 128, 256, 4),
    ("sddmm_vpu", 32, 512, 1), ("sddmm_vpu", 256, 256, 1),
    ("sddmm_vpu", 32, 256, 4),
]


@pytest.mark.parametrize("kernel,rows,tile,heads", VMEM_CASES,
                         ids=["-".join(map(str, c)) for c in VMEM_CASES])
def test_vmem_model_covers_what_mosaic_allocates(one_chip, monkeypatch,
                                                 kernel, rows, tile, heads):
    """Each kernel compiles for a v5e with its scoped VMEM limited to
    the tuner's modeled step (:mod:`repro.tune.model`), so a tile the
    model admits under the budget fits the chip. Mosaic lays the
    fetched-rows scratch out one sublane per row; the SpMM kernels'
    relayout of the rows takes up to 1.6× that scratch again, and
    ``spmm_mxu``'s heads stack a copy each."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels import sddmm_mxu, sddmm_vpu, spmm_mxu, spmm_vpu
    from repro.tune import model

    step = getattr(model, f"{kernel}_step_bytes")(rows, tile, heads=heads)
    pallas_call = pl.pallas_call

    def limited(*args, **kw):
        kw["compiler_params"] = pltpu.CompilerParams(vmem_limit_bytes=step)
        return pallas_call(*args, **kw)

    monkeypatch.setattr(pl, "pallas_call", limited)

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    nb, k, i32 = 64, 20_000, jnp.int32
    multi = heads > 1
    hd = tile // heads if multi else None
    hs = (heads,) if multi else ()
    if kernel == "spmm_mxu":
        fn = lambda v, c, b: spmm_mxu.spmm_mxu.__wrapped__(  # noqa: E731
            v, c, b, nt=tile, head_dim=hd, interpret=False)
        args = (s((nb, 8, rows) + hs), s((nb, rows), i32), s((k, tile)))
    elif kernel == "spmm_vpu":
        fn = lambda v, c, b, n: spmm_vpu.spmm_vpu.__wrapped__(  # noqa: E731
            v, c, b, n, nt=tile, head_dim=hd, interpret=False)
        args = (s((nb, rows) + hs), s((nb, rows), i32), s((k, tile)),
                s((nb,), i32))
    elif kernel == "sddmm_mxu":
        fn = lambda c, bm, w, x, y: sddmm_mxu.sddmm_mxu.__wrapped__(  # noqa: E731
            c, bm, w, x, y, kf_tile=tile, heads=heads if multi else None,
            head_dim=hd, interpret=False)
        args = (s((nb, rows), i32), s((nb, rows), jnp.uint32),
                s((nb,), i32), s((k, tile)), s((k, tile)))
    else:
        fn = lambda r, c, x, y: sddmm_vpu.sddmm_vpu.__wrapped__(  # noqa: E731
            r, c, x, y, kf_tile=tile, heads=heads if multi else None,
            head_dim=hd, interpret=False)
        args = (s((nb, rows), i32), s((nb, rows), i32), s((k, tile)),
                s((k, tile)))
    # A jit of the kernels' own functions (``__wrapped__``), so that no
    # earlier trace of the same shapes, made without the limit, is
    # reused.
    assert "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()
