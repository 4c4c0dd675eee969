"""``pallas_ms_per_step``: device milliseconds per step spent in Pallas
kernels: ops the trace records as custom calls with the target
``tpu_custom_call`` (told apart by that target, not by kernel name).
Nothing to read when the trace holds none. Moves ``step_s``."""


def read(ctx: dict) -> float | None:
    tr = ctx["trace"]
    if tr["pallas_s"] <= 0.0:
        return None
    return 1e3 * tr["pallas_s"] / tr["steps"]
