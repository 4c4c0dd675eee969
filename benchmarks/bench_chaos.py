"""Chaos benchmark: serving throughput and accounting under injected
faults (`BENCH_chaos.json`).

A seeded :class:`~repro.serve.faults.FaultPlan` storm
(``REPRO_FAULT_SEED``, default fixed — CI replays the identical
schedule) is driven through the resilient engine over the bench corpus.
Rows are **informational** (no ratio bars; the regression gate holds
the fault-free hot path via ``serve/fastpath_overhead`` instead):

* ``chaos/storm_mix`` — wall time of a flush with faults firing, with
  completion accounting (served / typed failures / faults injected);
* ``chaos/storm_bit_identical`` — every completed request matches its
  direct operator call bitwise, faults or not;
* ``chaos/degradation`` — where the survived requests were served
  (ladder rung histogram, retries);
* ``chaos/breaker_cycle`` — a latched fast-path fault drives one full
  open → probe → recover breaker cycle;
* ``chaos/deadline_storm`` — drop accounting when every deadline in a
  bucket has expired.
"""
from __future__ import annotations

import os

import numpy as np

FAULT_SEED = int(os.environ.get("REPRO_FAULT_SEED", "20260808"))


def run() -> list[tuple]:
    import jax.numpy as jnp

    from benchmarks.common import corpus, timeit
    from repro.serve import (
        FaultPlan,
        FaultRule,
        GraphRegistry,
        ResiliencePolicy,
        ServeError,
        SparseEngine,
    )

    rows = []
    rng = np.random.default_rng(0)
    mats = corpus(4)
    width = 32
    n_rounds = 8

    registry = GraphRegistry(max_graphs=len(mats),
                             width_buckets=(16, 32, 64),
                             panel_buckets=(1, 2, 4, 8))
    for name, a in mats.items():
        registry.register(a, name=name, ops=("spmm",), warm_widths=(width,))
    ops = {name: registry.resolve(name).op("spmm").op for name in mats}

    reqs = []
    for name, a in mats.items():
        for _ in range(n_rounds):
            reqs.append((name, jnp.asarray(
                rng.standard_normal((a.k, width)).astype(np.float32))))
    rng.shuffle(reqs)
    direct = [np.asarray(ops[name](b)) for name, b in reqs]

    # --- seeded storm over every ladder site of every graph
    sites = [(name, "spmm", s) for name in mats
             for s in ("fast", "single", "xla")]
    plan = FaultPlan.storm(FAULT_SEED, sites, n_faults=12, max_k=4,
                           kinds=("raise", "resource"), times=(1, 2))
    eng = SparseEngine(registry, max_queue=512, faults=plan,
                       sleep=lambda s: None)   # count, don't wait

    def storm_flush():
        rids = [eng.submit(name, "spmm", b=b) for name, b in reqs]
        return rids, eng.flush()

    t_storm = timeit(lambda: storm_flush()[1])
    rids, out = storm_flush()
    failed = sum(isinstance(out[r], ServeError) for r in rids)
    rows.append(("chaos/storm_mix", t_storm * 1e6,
                 f"{len(rids) - failed}of{len(rids)}_served_"
                 f"{len(plan.log)}faults_{failed}typed_failures"))
    ok = all(isinstance(out[r], ServeError)
             or np.array_equal(np.asarray(out[r]), want)
             for r, want in zip(rids, direct))
    rows.append(("chaos/storm_bit_identical", 0.0, str(bool(ok))))
    h = eng.health()
    served = h["degraded_served"]
    rows.append(("chaos/degradation", 0.0,
                 f"single{served.get('single', 0)}_"
                 f"xla{served.get('xla', 0)}_retries{h['retries']}"))

    # --- one full breaker cycle under a latched-then-healed fault
    name0, a0 = next(iter(mats.items()))
    policy = ResiliencePolicy(breaker_threshold=2, probe_after=2,
                              attempts_per_rung=1)
    plan2 = FaultPlan([FaultRule(kth=1, graph=name0, strategy="fast",
                                 times=4)])
    eng2 = SparseEngine(registry, resilience=policy, faults=plan2,
                        sleep=lambda s: None)
    b0 = jnp.asarray(rng.standard_normal((a0.k, width)).astype(np.float32))

    def cycle():
        for _ in range(10):
            eng2.submit(name0, "spmm", b=b0)
            eng2.flush()
            if eng2.health()["breakers"][f"{name0}/spmm"]["recoveries"]:
                break

    t_cycle = timeit(cycle, reps=1)
    br = eng2.health()["breakers"][f"{name0}/spmm"]
    rows.append(("chaos/breaker_cycle", t_cycle * 1e6,
                 f"opened{br['opened']}_probes{br['probes']}_"
                 f"recovered{br['recoveries']}_state_{br['state']}"))

    # --- deadline storm: expired requests drop with typed results
    class _Clock:
        t = 100.0

        def __call__(self):
            return self.t

    clk = _Clock()
    eng3 = SparseEngine(registry, clock=clk)
    dl_rids = [eng3.submit(name, "spmm", b=b, deadline_ms=5.0)
               for name, b in reqs[:8]]
    clk.t += 1.0
    out3 = eng3.flush()
    dropped = sum(isinstance(out3[r], ServeError) for r in dl_rids)
    dl = eng3.health()["deadline"]
    rows.append(("chaos/deadline_storm", 0.0,
                 f"{dropped}of{len(dl_rids)}_dropped_"
                 f"missrate{dl['miss_rate']:.2f}"))
    return rows


if __name__ == "__main__":
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")
