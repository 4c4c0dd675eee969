"""``step_mfu``: the FLOPs a training step requires (``bench/counts.py``,
from the layer equations and the graph's sizes) per second of the traced
window's steps, as a percentage of the chips' peak (``bench/peaks.json``).
Moves ``step_s``."""


def read(ctx: dict) -> float:
    achieved = ctx["flops_per_step"] / ctx["step_s"]
    return 100.0 * achieved / (ctx["peaks"]["flops_per_s"] * ctx["chips"])
