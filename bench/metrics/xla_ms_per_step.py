"""``xla_ms_per_step``: device-busy milliseconds per step outside the
Pallas kernels: the combine scatters, gathers and padding, the dense
layers, the softmax and the loss. Moves ``step_s``."""


def read(ctx: dict) -> float | None:
    tr = ctx["trace"]
    if tr["busy_s"] <= 0.0:
        return None
    return 1e3 * (tr["busy_s"] - tr["pallas_s"]) / tr["steps"]
