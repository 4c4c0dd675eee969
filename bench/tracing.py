"""Device trace of the timed window, and its reduction to numbers.

A traced run wraps the window in the JAX profiler. The profile's
``.xplane.pb`` is read with ``jax.profiler.ProfileData`` and cut down by
:func:`extract` to what the metrics need:

- per TPU device plane, the events of its ``XLA Ops`` line: the HLO
  instruction's short name and output shape, start and duration in
  nanoseconds, and whether it is a Pallas kernel (a custom call whose
  target is ``tpu_custom_call``);
- the benchmark's own host annotations (names starting ``bench.``), on
  the same clock: ``bench.window`` around the whole window, and
  ``bench.dispatch``, ``bench.wait``, ``bench.drain`` inside it.

:func:`reduce` turns that into the device's busy time (the union of its
op intervals inside the window), the Pallas share of it, and the
``breakdown``: the ten device ops that took most time, and the ten
longest idle gaps, each named by the host annotation it fell in.
"""
from __future__ import annotations

import contextlib
import os
import re
import shutil
import tempfile

_CUSTOM = 'custom_call_target="tpu_custom_call"'
_LAYOUT = re.compile(r"\{[^}]*\}")


def short_name(hlo: str) -> str:
    """``%spmm_vpu.12 = f32[171824,256]{1,0:T(8,128)} custom-call(...)``
    → ``spmm_vpu.12 f32[171824,256]``."""
    name, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:80]
    shape = rest.split(" ", 1)[0]
    shape = "tuple" if shape.startswith("(") else _LAYOUT.sub("", shape)
    return f"{name.lstrip('%')} {shape}"


def extract(xplane_path: str) -> dict:
    """The device ops and ``bench.`` host annotations of one profile."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    ops.append([short_name(ev.name), ev.start_ns,
                                ev.duration_ns, int(_CUSTOM in ev.name)])
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append([ev.name, ev.start_ns, ev.duration_ns])
    return {"devices": devices, "host": host}


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def _length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def _label(gap: tuple[float, float], host: list) -> str:
    """The innermost ``bench.`` annotation that overlaps the gap most."""
    best, best_overlap = "host: outside bench annotations", 0.0
    for name, start, dur in host:
        if name == "bench.window":
            continue
        overlap = min(gap[1], start + dur) - max(gap[0], start)
        if overlap > best_overlap:
            best, best_overlap = f"host: {name}", overlap
    return best


def reduce(trace: dict, steps: int) -> dict:
    """Busy and Pallas time inside the window (averaged over the traced
    devices), and the breakdown. Times in seconds."""
    windows = [(s, s + d) for n, s, d in trace["host"]
               if n == "bench.window"]
    if not windows:
        raise ValueError("the trace holds no bench.window annotation")
    w_lo, w_hi = windows[0]
    busy, pallas, per_op, gaps = [], [], {}, []
    for ops in trace["devices"].values():
        spans = _union(_clip([(s, s + d) for _, s, d, _ in ops], w_lo, w_hi))
        kern = _union(_clip([(s, s + d) for _, s, d, c in ops if c],
                            w_lo, w_hi))
        busy.append(_length(spans))
        pallas.append(_length(kern))
        for name, s, d, _ in ops:
            inside = _length(_clip([(s, s + d)], w_lo, w_hi))
            if inside:
                per_op[name] = per_op.get(name, 0.0) + inside
        edges = [w_lo] + [x for iv in spans for x in iv] + [w_hi]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    n_dev = max(len(busy), 1)
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    return {
        "window_s": (w_hi - w_lo) * 1e-9,
        "busy_s": sum(busy) / n_dev * 1e-9,
        "pallas_s": sum(pallas) / n_dev * 1e-9,
        "devices": len(busy),
        "steps": steps,
        "breakdown": {
            "device_ops": [[n, t * 1e-9 / n_dev] for n, t in top_ops],
            "idle_gaps": [[_label(g, trace["host"]), (g[1] - g[0]) * 1e-9]
                          for g in top_gaps],
        },
    }


class DeviceTrace:
    """Profiles the ``with`` block when enabled; :meth:`annotate` marks
    host spans on the profiler's clock (a no-op when disabled)."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir = None

    def __enter__(self):
        if self.enabled:
            import jax

            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self.dir)
        return self

    def __exit__(self, *exc):
        if self.enabled:
            import jax

            jax.profiler.stop_trace()
        return False

    def annotate(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def reduce(self, steps: int) -> dict:
        """Read the profile, delete it, and reduce it."""
        try:
            found = [os.path.join(d, f) for d, _, fs in os.walk(self.dir)
                     for f in fs if f.endswith(".xplane.pb")]
            if len(found) != 1:
                raise FileNotFoundError(
                    f"expected one .xplane.pb under {self.dir}, "
                    f"found {found}")
            return reduce(extract(found[0]), steps)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
