"""Plan/execution explainer: the paper's arguments as inspectable numbers.

Libra's performance case rests on structural quantities — the 2D-aware
TC/VPU split (TC fraction, window density), the §4.3 Ts/Cs segment
decomposition and its balance residue, padding waste of the condensed
formats, and the occupancy model's VMEM sizing. :func:`explain_spmm` /
:func:`explain_sddmm` report all of them for a prepared operator, plan,
or registry entry — predicted (tuner model) side by side with measured
(wall time, HLO flops/bytes from the compiled executable) — as a dict
and a rendered text table (:func:`render_table`).

Heavy imports (jax, the kernels) happen lazily inside the measuring
paths, so ``repro.obs`` stays importable everywhere.
"""
from __future__ import annotations

import numpy as np

from repro.obs.trace import get_tracer

_DENSITY_BINS = 8


def _window_hist(plan, a=None) -> dict:
    """Per-window density histogram. With the source matrix, the full
    Fig.-1 statistic (8×1 vector occupancy, 1..8 nnz); from the plan
    alone, occupancy of the condensed TC bitmaps (the residue stream has
    no vector structure left)."""
    from repro.core.formats import WINDOW

    if a is not None:
        from repro.tune.model import matrix_features

        feat = matrix_features(a)
        hist = feat.win_vec_hist.sum(axis=0)[1:]  # vectors with 1..8 nnz
        return {
            "vector_occupancy": [int(c) for c in hist],
            "window_density": float(feat.window_density),
            "source": "matrix",
        }
    bits = np.asarray(plan.tc.bitmap, np.uint32).reshape(-1)
    pop = np.zeros_like(bits, np.int64)
    for s in range(WINDOW):
        pop += (bits >> np.uint32(s)) & np.uint32(1)
    pop = pop[pop > 0]
    hist = np.bincount(pop, minlength=WINDOW + 1)[1:WINDOW + 1]
    return {
        "vector_occupancy": [int(c) for c in hist],
        "window_density": float(pop.mean() / WINDOW) if pop.size else 0.0,
        "source": "tc_bitmap",
    }


def _segment_report(plan) -> dict:
    """§4.3 segment counts, atomic fractions, and the LPT balance
    residue (:func:`repro.core.balance.balance_report`) of each stream's
    segment sizes — the quantity shard balancing minimizes."""
    from repro.core.balance import balance_report

    out: dict = {}
    for stream in ("tc", "vpu"):
        seg = plan.meta.get(f"{stream}_segments")
        if seg is None or not seg.nseg:
            out[stream] = None
            continue
        out[stream] = {
            "nseg": int(seg.nseg),
            "limit": int(seg.limit),
            "atomic_frac": float(np.mean(seg.atomic)),
            "mean_size": float(np.mean(seg.sizes)),
            "balance": balance_report(np.asarray(seg.sizes, np.int64), 8),
        }
    out["seg_spt"] = int(plan.meta.get("seg_spt", 1))
    return out


def plan_counts(plan, kind: str) -> dict:
    """What the condensed formats hold, stream by stream: the MXU
    stream's non-zeros, its blocks, their width ``bk`` and their cells;
    the VPU stream's real elements, its tiles (the segments the kernel
    launches over), their width ``cs`` and their slots (tiles × ``cs``).
    ``vpu_fetches`` is the B rows the VPU kernel fetches a lane tile:
    the sum of the SpMM segment lengths it launches with, every slot for
    SDDMM.
    :func:`_padding_report` derives the padding from these, and the
    ``preprocess.spmm``/``preprocess.sddmm`` spans of an enabled tracer
    carry them as attributes."""
    from repro.core.formats import spmm_vpu_seg_len

    tc, vpu = plan.tc, plan.vpu
    if kind == "spmm":
        slots, vpu_nnz = vpu.vals, int(vpu.nnz)
        fetches = int(spmm_vpu_seg_len(plan).sum())
    else:  # COOTiles: mask marks real elements
        slots, vpu_nnz = vpu.mask, int(vpu.mask.sum())
        fetches = int(slots.size)
    return {
        "tc_nnz": int(tc.nnz),
        "mxu_blocks": int(tc.cols.shape[0]),
        "bk": int(tc.cols.shape[1]),
        "tc_cells": int(tc.vals.size),
        "vpu_nnz": vpu_nnz,
        "vpu_segments": int(slots.shape[0]),
        "cs": int(slots.shape[1]),
        "vpu_slots": int(slots.size),
        "vpu_fetches": fetches,
    }


def head_counts(heads: int, head_dim: int, tile: int) -> dict:
    """The head layout of one multi-head sparse call: ``heads`` heads of
    ``head_dim`` features each, laid contiguously (a head starts
    ``head_stride`` features after the one before it, which equals
    ``head_dim`` by construction: the layout pads no head, only the last
    lane tile), padded to whole
    ``tile``-lane tiles; ``lane_fill`` is the share of the padded width
    that holds heads, in %. The multi-head calls of
    :class:`repro.models.gnn.GraphOps` carry these as the attributes of
    their ``graphops.heads`` spans on an enabled tracer."""
    width = heads * head_dim
    padded = -(-width // tile) * tile
    return {"heads": heads, "head_dim": head_dim, "head_stride": head_dim,
            "lane_fill": 100.0 * width / padded}


def tile_counts(width: int, tile: int) -> dict:
    """The lane tiling of one sparse call of ``width`` dense columns at
    lane tile ``tile``: ``lane_tiles`` tiles cover the width, and each
    real non-zero costs that many row copies in the call. The sparse
    calls of :class:`repro.models.gnn.GraphOps` carry these as the
    attributes of their ``graphops.tile`` spans on an enabled tracer."""
    return {"width": width, "tile": tile, "lane_tiles": -(-width // tile)}


def _padding_report(plan, kind: str) -> dict:
    """Zero padding materialized by the condensed formats (bytes the
    kernels stream but the matrix never had)."""
    c = plan_counts(plan, kind)
    tc_pad = c["tc_cells"] - c["tc_nnz"]
    vpu_pad = c["vpu_slots"] - c["vpu_nnz"]
    return {
        "tc_padded_zeros": tc_pad,
        "tc_pad_frac": tc_pad / max(c["tc_cells"], 1),
        "vpu_padded_zeros": vpu_pad,
        "vpu_pad_frac": vpu_pad / max(c["vpu_slots"], 1),
        "total_pad_frac": (tc_pad + vpu_pad)
        / max(c["tc_cells"] + c["vpu_slots"], 1),
    }


def _occupancy_report(cfg, plan, kind: str) -> dict | None:
    """Tuner-predicted VMEM footprint / pipeline depth of one grid step
    for the plan as built (``None`` when no config is known)."""
    if cfg is None:
        return None
    from repro.tune.model import (occupancy_report, vmem_sddmm_bytes,
                                  vmem_spmm_bytes)

    ts = int(plan.vpu.ts)
    if kind == "spmm":
        step = vmem_spmm_bytes(cfg, bk=int(plan.tc.bk), ts=ts)
    else:
        step = vmem_sddmm_bytes(cfg, bk=int(plan.tc.bk), ts=ts)
    return occupancy_report(step)


def _measure(op, kind: str, *, width: int, backend: str, reps: int,
             timer=None) -> dict:
    """Measured side: median apply wall time plus HLO flops / HBM bytes
    of the compiled executable when one is cached for the shape."""
    import time

    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    if kind == "spmm":
        args = (jnp.asarray(rng.standard_normal(
            (op.k, width)).astype(np.float32)),)
    else:
        args = (jnp.asarray(rng.standard_normal(
                    (op.m, width)).astype(np.float32)),
                jnp.asarray(rng.standard_normal(
                    (op.k, width)).astype(np.float32)))

    def call():
        return op(*args, backend=backend)

    if timer is None:
        def timer(fn):
            jax.block_until_ready(fn())     # compile/warm
            ts = []
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fn())
                ts.append(time.perf_counter() - t0)
            return float(np.median(ts))

    wall_s = timer(call)
    out = {"wall_s": wall_s, "width": width, "backend": backend}
    key = (width, "float32", backend, True)
    compiled = op._apply_cache.get(key)
    if compiled is None and op._apply_cache:
        compiled = next(iter(op._apply_cache.values()))
    if compiled is not None:
        try:
            from repro.launch.hlo_analysis import analyze_hlo

            st = analyze_hlo(compiled.as_text())
            out["hlo_flops"] = float(st.flops)
            out["hlo_hbm_bytes"] = float(st.hbm_bytes)
            if wall_s > 0:
                out["hlo_gflops_per_s"] = st.flops / wall_s / 1e9
        except Exception:  # HLO text shape drift must never kill explain
            pass
    return out


def explain_plan(plan, *, cfg=None, a=None, kind: str | None = None) -> dict:
    """Structural report for one prepared plan (no execution).

    ``cfg`` (the :class:`~repro.tune.model.TuneConfig` the plan was
    built with) adds the predicted-occupancy section; ``a`` (the source
    matrix) upgrades the density histogram to full vector resolution.
    """
    from repro.core.formats import SpMMPlan

    if kind is None:
        kind = "spmm" if isinstance(plan, SpMMPlan) else "sddmm"
    meta = plan.meta
    return {
        "kind": kind,
        "shape": {"m": plan.m, "k": plan.k, "nnz": plan.nnz},
        "threshold": plan.threshold,
        "tc_fraction": float(meta.get("tc_ratio", 0.0)),
        "tc_nnz": int(meta.get("tc_nnz", 0)),
        "vpu_nnz": int(meta.get("vpu_nnz", 0)),
        "density_hist": _window_hist(plan, a),
        "reorder": meta.get("reorder"),
        "segments": _segment_report(plan),
        "padding": _padding_report(plan, kind),
        "occupancy": _occupancy_report(cfg, plan, kind),
        "tune_source": getattr(cfg, "source", None),
        "measured": None,
    }


def _explain_op(op, kind: str, *, a=None, measure: bool, width: int,
                backend: str, reps: int, timer=None) -> dict:
    with get_tracer().span("obs.explain", kind=kind):
        report = explain_plan(op.plan, cfg=op.tune_config, a=a, kind=kind)
        arrays = getattr(op, "arrays", None)
        if hasattr(arrays, "view_nbytes"):
            # Per-view resident/lazy device-byte status (PlanArrays).
            report["memory"] = arrays.memory()
        if measure:
            report["measured"] = _measure(op, kind, width=width,
                                          backend=backend, reps=reps,
                                          timer=timer)
        return report


def explain_spmm(target, *, a=None, measure: bool = False, width: int = 32,
                 backend: str = "xla", reps: int = 3, timer=None,
                 **op_kwargs) -> dict:
    """Explain an SpMM plan/operator/matrix.

    ``target`` may be a :class:`~repro.core.spmm.LibraSpMM`, a prepared
    :class:`~repro.core.formats.SpMMPlan`, or a raw
    :class:`~repro.sparse.matrix.SparseCSR` (an operator is constructed
    with ``**op_kwargs``). ``measure=True`` times the apply and attaches
    HLO flops/bytes when a compiled executable is available.
    """
    from repro.core.formats import SpMMPlan
    from repro.core.spmm import LibraSpMM
    from repro.sparse.matrix import SparseCSR

    if isinstance(target, SpMMPlan):
        return explain_plan(target, a=a, kind="spmm")
    if isinstance(target, SparseCSR):
        target, a = LibraSpMM(target, **op_kwargs), target
    return _explain_op(target, "spmm", a=a, measure=measure, width=width,
                       backend=backend, reps=reps, timer=timer)


def explain_sddmm(target, *, a=None, measure: bool = False, width: int = 32,
                  backend: str = "xla", reps: int = 3, timer=None,
                  **op_kwargs) -> dict:
    """SDDMM counterpart of :func:`explain_spmm`."""
    from repro.core.formats import SDDMMPlan
    from repro.core.sddmm import LibraSDDMM
    from repro.sparse.matrix import SparseCSR

    if isinstance(target, SDDMMPlan):
        return explain_plan(target, a=a, kind="sddmm")
    if isinstance(target, SparseCSR):
        target, a = LibraSDDMM(target, **op_kwargs), target
    return _explain_op(target, "sddmm", a=a, measure=measure, width=width,
                       backend=backend, reps=reps, timer=timer)


def explain_entry(registry, name: str, op: str = "spmm", **kw) -> dict:
    """Explain a :class:`~repro.serve.registry.GraphRegistry` entry's
    operator (batched entries only — sharded entries carry per-shard
    plans; explain those via :func:`explain_partition`)."""
    entry = registry.resolve(name)
    fn = entry.op(op)
    if entry.sharded:
        raise ValueError(f"{name!r} is sharded; use explain_partition on "
                         f"its SpMMPartition")
    report = (explain_spmm if op == "spmm" else explain_sddmm)(fn.op, **kw)
    report["registry"] = {"name": name, "key": entry.key[:10],
                          "mode": entry.mode, "warmed": entry.warmed}
    return report


def explain_partition(part) -> dict:
    """Shard-level report for a :class:`~repro.dist.partition`
    partition: per-shard nnz/segment balance and halo waste."""
    meta = part.meta
    halo = meta.get("halo_rows", [])
    nnz = meta.get("shard_nnz", [])
    return {
        "kind": "partition",
        "n_shards": len(nnz),
        "shard_nnz": [int(x) for x in nnz],
        "reorder": meta.get("reorder"),
        "nnz_balance": meta.get("balance"),
        "segment_balance": meta.get("segment_balance"),
        "shard_segments": meta.get("shard_segments"),
        "halo_rows": [int(x) for x in halo],
        "halo_waste_frac": float(sum(halo)) / max(float(sum(nnz)), 1.0),
    }


# ------------------------------------------------------------ render ---
def _fmt(v) -> str:
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v)


def render_table(report: dict, *, title: str | None = None) -> str:
    """Render an explain report as an aligned two-column text table."""
    rows: list[tuple[str, str]] = []
    kind = report.get("kind", "?")
    shape = report.get("shape", {})
    rows.append(("operator", kind))
    if shape:
        rows.append(("shape", f"{shape['m']}x{shape['k']} "
                              f"nnz={shape['nnz']}"))
    if "threshold" in report:
        rows.append(("threshold", _fmt(report["threshold"])))
    if "tc_fraction" in report:
        rows.append(("tc_fraction", _fmt(report["tc_fraction"])))
        rows.append(("tc/vpu nnz", f"{report['tc_nnz']}/"
                                   f"{report['vpu_nnz']}"))
    dh = report.get("density_hist")
    if dh:
        rows.append(("window_density", _fmt(dh["window_density"])))
        rows.append(("vec_occupancy[1..8]",
                     " ".join(str(c) for c in dh["vector_occupancy"])))
    ro = report.get("reorder")
    if ro:
        if ro.get("enabled"):
            rows.append(("reorder", f"chosen ({ro.get('mode', '?')}): "
                                    f"tc_frac {ro['tc_frac_before']:.3f}"
                                    f" -> {ro['tc_frac_after']:.3f}"))
            rows.append(("reorder_density",
                         f"{ro['window_density_before']:.3f} -> "
                         f"{ro['window_density_after']:.3f}"))
            if "occupancy_before" in ro:
                rows.append(("occupancy_before[1..8]",
                             " ".join(str(c)
                                      for c in ro["occupancy_before"])))
                rows.append(("occupancy_after[1..8]",
                             " ".join(str(c)
                                      for c in ro["occupancy_after"])))
        else:
            why = (f"gain {ro['gain']:.3f}" if "gain" in ro
                   else ro.get("mode", "off"))
            rows.append(("reorder", f"skipped ({why})"))
    segs = report.get("segments")
    if segs:
        for stream in ("tc", "vpu"):
            s = segs.get(stream)
            if s is None:
                rows.append((f"{stream}_segments", "off"))
            else:
                rows.append((f"{stream}_segments",
                             f"{s['nseg']} (limit {s['limit']}, atomic "
                             f"{s['atomic_frac']:.2f}, max/mean "
                             f"{s['balance']['max_over_mean']:.3f})"))
    pad = report.get("padding")
    if pad:
        rows.append(("padding", f"tc {pad['tc_pad_frac']:.3f}, vpu "
                                f"{pad['vpu_pad_frac']:.3f}, total "
                                f"{pad['total_pad_frac']:.3f}"))
    occ = report.get("occupancy")
    if occ:
        rows.append(("vmem_per_step", f"{occ['bytes_per_step']} B "
                                      f"(budget {occ['budget_bytes']})"))
        rows.append(("pipeline_depth",
                     f"{occ['pipeline_depth']} "
                     f"({'fits' if occ['fits'] else 'OVER BUDGET'})"))
    mem = report.get("memory")
    if mem:
        for view, st in sorted(mem["views"].items()):
            if st["resident_keys"] == 0:
                status = "lazy"
            elif st["resident_keys"] == st["keys"]:
                status = "resident"
            else:
                status = "partial"
            rows.append((f"mem_{view}",
                         f"{status} {st['resident_bytes']}/{st['bytes']} B "
                         f"({st['resident_keys']}/{st['keys']} arrays)"))
        rows.append(("mem_resident", f"{mem['resident_bytes']}/"
                                     f"{mem['total_bytes']} B"))
    meas = report.get("measured")
    if meas:
        rows.append(("measured_wall", f"{meas['wall_s'] * 1e6:.1f} us "
                                      f"(n={meas['width']}, "
                                      f"{meas['backend']})"))
        if "hlo_flops" in meas:
            rows.append(("hlo_flops", _fmt(meas["hlo_flops"])))
            rows.append(("hlo_hbm_bytes", _fmt(meas["hlo_hbm_bytes"])))
    if report.get("kind") == "partition":
        rows = [("operator", "partition"),
                ("n_shards", _fmt(report["n_shards"])),
                ("shard_nnz", " ".join(map(str, report["shard_nnz"]))),
                ("nnz max/mean",
                 _fmt(report["nnz_balance"]["max_over_mean"])),
                ("halo_rows", " ".join(map(str, report["halo_rows"]))),
                ("halo_waste_frac", _fmt(report["halo_waste_frac"]))]
        sb = report.get("segment_balance")
        if sb:
            rows.append(("segment max/mean", _fmt(sb["max_over_mean"])))
        ro = report.get("reorder")
        if ro:
            rows.append(("reorder",
                         (f"chosen: tc_frac {ro['tc_frac_before']:.3f} -> "
                          f"{ro['tc_frac_after']:.3f}")
                         if ro.get("enabled") else "skipped"))
    w = max(len(k) for k, _ in rows)
    lines = [f"{k:>{w}} | {v}" for k, v in rows]
    bar = "-" * max(len(line) for line in lines)
    head = [title, bar] if title else [bar]
    return "\n".join(head + lines + [bar])
