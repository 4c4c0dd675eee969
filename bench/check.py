"""The comparison that decides ``correct``: the program's first three
training steps against the plain float32 reference.

The window's own step object runs steps 1 to 3 (step 1 is set-up's
warm-up, steps 2 and 3 the window's first two). From them the harness
keeps the three losses, the parameters before step 1 (``p0``), after
step 1 (``p1``) and after step 3 (``p3``). The reference (the
configuration's ``reference_logits`` under a plain cross-entropy and the
same SGD update, at ``highest`` matmul precision) runs the same three
steps from ``p0``. Five numbers are compared, each against its limit:

- ``loss_gap``: the largest relative gap of the three losses;
- ``grad_gap``: the first gradient as the optimizer got it,
  ``(p0 - p1) / lr``, by the worst leaf: the gap between the program's
  and the reference's norm of that leaf, over the larger of the
  reference's norm of the leaf and of the median leaf;
- ``delta_gap``: the same for the parameters' change ``p3 - p0``;
- ``grad_diff`` and ``delta_diff``: the same two leaves by the norm of
  their difference, ``‖prog - ref‖`` over the same scale. A gap of norms
  is blind to a change of direction; this sees it.

Leaves whose reference gradient norm is under a thousandth of the median
leaf's are left out of the four leaf measures: they move by round-off
alone.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: Leaves whose reference gradient norm is under this share of the
#: median leaf's are left out of the leaf measures.
DEAD_LEAF = 1e-3


def cross_entropy(logits, labels, mask=None):
    """Mean negative log-likelihood of ``labels`` (over ``mask``'s rows)."""
    lp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(lp, labels[:, None], axis=1)[:, 0]
    if mask is None:
        return nll.mean()
    return jnp.sum(jnp.where(mask, nll, 0)) / jnp.sum(mask)


def reference_step(model, graph: dict, inputs: dict, lr: float,
                   dtype=jnp.float32, mask=None):
    """``params -> (params, loss)``: the configuration's reference forward
    pass, a cross-entropy and an SGD update, jitted with the graph and
    the inputs as arguments (so no array is baked into the program).
    ``dtype`` below float32 makes the control: the loss and its gradient
    are computed with every operand in ``dtype``; the parameters stay
    float32. ``mask`` takes the mean over its rows only (a planted
    fault)."""
    nodes = graph["nodes"]
    arrays = {k: v for k, v in graph.items() if k != "nodes"}

    def cast(tree):
        return jax.tree.map(
            lambda x: x.astype(dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)

    def loss_fn(params, arrs, inp, m):
        logits = model.reference_logits(cast(params),
                                        dict(arrs, nodes=nodes), cast(inp))
        return cross_entropy(logits, inp["labels"], m)

    @jax.jit
    def step(params, arrs, inp, m):
        loss, grads = jax.value_and_grad(loss_fn)(params, arrs, inp, m)
        new = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype), params,
                           grads)
        return new, loss.astype(jnp.float32)

    return lambda params: step(params, arrays, inputs, mask)


def run_reference(step, p0, n: int = 3):
    """``n`` reference steps from ``p0`` at ``highest`` matmul precision:
    the losses, the parameters after step 1 and after step ``n``."""
    with jax.default_matmul_precision("highest"):
        params, losses, p1 = p0, [], None
        for i in range(n):
            params, loss = step(params)
            losses.append(float(loss))
            if i == 0:
                p1 = params
    return losses, p1, params


def _leaf_norms(tree) -> list[float]:
    return [float(np.linalg.norm(np.asarray(x, np.float64)))
            for x in jax.tree.leaves(tree)]


def leaf_gap(prog: list[float], ref: list[float], live: list[bool]) -> float:
    """Worst leaf's gap of norms, over the larger of its reference norm
    and the median leaf's."""
    med = float(np.median(ref))
    gaps = [abs(p - r) / max(r, med, 1e-30)
            for p, r, ok in zip(prog, ref, live) if ok]
    return max(gaps) if gaps else 0.0


def leaf_diff(diff: list[float], ref: list[float], live: list[bool]
              ) -> float:
    """Worst leaf's norm of the difference, over the larger of its
    reference norm and the median leaf's."""
    med = float(np.median(ref))
    out = [d / max(r, med, 1e-30) for d, r, ok in zip(diff, ref, live) if ok]
    return max(out) if out else 0.0


def gaps(lr: float, prog: dict, ref: dict) -> tuple[dict, dict]:
    """The compared numbers from the program's and the reference's
    ``losses``, ``p0``, ``p1`` and ``p3``, and the leaf norms they were
    taken from (for the record)."""
    def diff(a, b, scale=1.0):
        return jax.tree.map(
            lambda x, y: (np.asarray(x, np.float64)
                          - np.asarray(y, np.float64)) * scale, a, b)

    g_prog = diff(prog["p0"], prog["p1"], 1.0 / lr)
    g_ref = diff(ref["p0"], ref["p1"], 1.0 / lr)
    d_prog = diff(prog["p3"], prog["p0"])
    d_ref = diff(ref["p3"], ref["p0"])
    g_ref_n = _leaf_norms(g_ref)
    d_ref_n = _leaf_norms(d_ref)
    med = float(np.median(g_ref_n))
    live = [g >= DEAD_LEAF * med for g in g_ref_n]
    g_prog_n = _leaf_norms(g_prog)
    d_prog_n = _leaf_norms(d_prog)
    g_diff_n = _leaf_norms(diff(g_prog, g_ref))
    d_diff_n = _leaf_norms(diff(d_prog, d_ref))
    loss_gap = max(abs(a - b) / max(abs(b), 1e-30)
                   for a, b in zip(prog["losses"], ref["losses"]))
    return {"loss_gap": float(loss_gap),
            "grad_gap": leaf_gap(g_prog_n, g_ref_n, live),
            "delta_gap": leaf_gap(d_prog_n, d_ref_n, live),
            "grad_diff": leaf_diff(g_diff_n, g_ref_n, live),
            "delta_diff": leaf_diff(d_diff_n, d_ref_n, live)}, {
        "grad_norms": {"program": g_prog_n, "reference": g_ref_n,
                       "difference": g_diff_n},
        "delta_norms": {"program": d_prog_n, "reference": d_ref_n,
                        "difference": d_diff_n},
        "live": live}


def verdict(numbers: dict[str, float], limits: dict[str, float]
            ) -> tuple[bool, dict]:
    """``correct`` and each number beside its limit. A number that is not
    finite, or that the limits file does not name, fails."""
    shown = {k: {"value": v, "limit": limits.get(k)}
             for k, v in numbers.items()}
    ok = all(k in limits and np.isfinite(v) and v <= limits[k]
             for k, v in numbers.items())
    return bool(ok), shown
