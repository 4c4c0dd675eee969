"""``device_idle``: the share of the traced window in which no operation
ran on the device, averaged over the chips used. Moves ``step_s``."""


def read(ctx: dict) -> float:
    tr = ctx["trace"]
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
