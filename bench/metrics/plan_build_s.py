"""``plan_build_s``: host seconds of ``GraphOps(...)``, the plan build of
A, Aᵀ and the SDDMM mask (reorder, tune, preprocess), as users pay it
once per graph. Moves ``setup_s``."""


def read(ctx: dict) -> float:
    return ctx["plan_build_s"]
