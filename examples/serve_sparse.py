"""Sparse-operator serving demo: two graphs, many tenants, one engine.

Registers two graphs (a GNN-style power-law graph and a FEM-style mixed
matrix) in a :class:`~repro.serve.registry.GraphRegistry`, warms the
AOT executables, then drives a mixed burst of SpMM/SDDMM requests from
three "tenants" through the panel-bucketed
:class:`~repro.serve.engine.SparseEngine` — plus a trained-GCN
node-scoring round through :class:`~repro.serve.gnn_service.GNNService`
— and prints the serving stats (throughput, padding waste, bucket
occupancy, cache hits).

A fourth "chaotic" tenant then demonstrates the resilience layer: its
requests carry deadlines, and an injected
:class:`~repro.serve.faults.FaultPlan` crashes the fast packed apply —
the engine degrades down the bit-equivalent ladder (packed → singles →
xla), the expired request is dropped with a typed
``DeadlineExceeded`` result, and ``engine.health()`` shows the
breaker/degradation accounting.

The whole demo runs under a :class:`~repro.obs.trace.Tracer`: at the
end it prints the engine's Prometheus exposition and dumps the full
request lifecycle (``serve.admit`` → ``serve.flush`` → ``bucket`` →
``execute`` → ``apply`` → ``serve.complete``, linked per request by
Perfetto flow events) as a Chrome-trace JSON you can open in Perfetto
— then scrapes the same metrics back over HTTP from the engine's
zero-dependency observability endpoint
(:meth:`~repro.serve.engine.SparseEngine.serve_http`: ``/metrics``,
``/health``, ``/explain/<graph>``).

    PYTHONPATH=src python examples/serve_sparse.py
"""
import json
import os
import tempfile
import urllib.request

import numpy as np
import jax
import jax.numpy as jnp

from repro.models import gnn as mgnn
from repro.obs import Tracer
from repro.serve import (
    FaultPlan,
    FaultRule,
    GNNService,
    GraphRegistry,
    ServeError,
    SparseEngine,
)
from repro.sparse.generate import mixed_csr, power_law_csr


def main() -> None:
    rng = np.random.default_rng(0)
    graph = power_law_csr(1024, 1024, 12.0, seed=1)   # social-graph regime
    fem = mixed_csr(768, 640, seed=2)                 # FEM/hybrid regime

    registry = GraphRegistry(max_graphs=8, width_buckets=(32, 64, 128),
                             panel_buckets=(1, 2, 4, 8))
    registry.register(graph, name="tenantA/social", warm_widths=(64,))
    registry.register(fem, name="tenantB/fem")
    registry.register(graph, name="tenantC/social-alias")  # shared plan

    # trace the whole serving session: every request's lifecycle shows
    # up as serve.admit/flush/bucket/execute/apply spans + a
    # serve.complete marker per answered rid
    tracer = Tracer()
    engine = SparseEngine(registry, tracer=tracer)

    # --- a mixed burst: three tenants, ragged widths, both operators
    rids = {}
    for i in range(6):
        b = jnp.asarray(rng.standard_normal(
            (graph.k, (48, 64, 57)[i % 3])).astype(np.float32))
        who = ("tenantA/social", "tenantC/social-alias")[i % 2]
        rids[engine.submit(who, "spmm", b=b)] = who
    for i in range(3):
        b = jnp.asarray(rng.standard_normal(
            (fem.k, 96)).astype(np.float32))
        rids[engine.submit("tenantB/fem", "spmm", b=b)] = "tenantB/fem"
    x = jnp.asarray(rng.standard_normal((fem.m, 32)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((fem.k, 32)).astype(np.float32))
    rids[engine.submit("tenantB/fem", "sddmm", x=x, y=y)] = "tenantB/fem"

    results = engine.flush()
    assert sorted(results) == sorted(rids)
    print(f"served {len(results)} requests "
          f"({sum(v.size for v in results.values())} output elements)")

    # --- trained-GCN scoring through the same engine
    service = GNNService(engine)
    params = mgnn.init_gcn(jax.random.PRNGKey(0), [64, 64, 16])
    service.register_gcn("tenantA/gcn", graph, params)
    feats = jnp.asarray(rng.standard_normal(
        (graph.m, 64)).astype(np.float32))
    s1 = service.submit("tenantA/gcn", feats, node_ids=np.arange(10))
    s2 = service.submit("tenantA/gcn", feats * 0.5, node_ids=np.arange(10))
    scores = service.flush()
    print(f"gcn scores for 10 nodes, 2 concurrent requests: "
          f"{np.asarray(scores[s1])[0, :4].round(3).tolist()} ...")
    assert scores[s2].shape == (10, 16)

    # --- tenant D: deadlines + an injected fast-path fault. The engine
    #     is resilient by default; the fault plan makes the packed apply
    #     crash once, so the bucket degrades to per-request singles
    #     (bit-identical results), while a request admitted with an
    #     already-hopeless deadline is dropped with a typed result.
    plan = FaultPlan([FaultRule(kth=1, graph="tenantB/fem", op="spmm",
                                strategy="fast")])
    engine.faults = plan
    good = [engine.submit("tenantB/fem", "spmm",
                          b=jnp.asarray(rng.standard_normal(
                              (fem.k, 64)).astype(np.float32)),
                          deadline_ms=10_000.0) for _ in range(3)]
    import time as _time

    doomed = engine.submit("tenantB/fem", "spmm",
                           b=jnp.asarray(rng.standard_normal(
                               (fem.k, 64)).astype(np.float32)),
                           deadline_ms=0.5)
    _time.sleep(0.005)                       # let the tight deadline die
    out = engine.flush()
    engine.faults = None
    assert all(not isinstance(out[r], ServeError) for r in good)
    assert isinstance(out[doomed], ServeError)
    print(f"\nchaotic tenant: {len(good)} requests survived an injected "
          f"fast-path crash (served degraded), 1 dropped: "
          f"{out[doomed].reason}")
    h = engine.health()
    print("--- engine health ---")
    print(f"{'breakers':>20}: "
          f"{ {k: v['state'] for k, v in h['breakers'].items()} }")
    print(f"{'degraded_served':>20}: {h['degraded_served']}")
    print(f"{'failures':>20}: {h['failures']}")
    print(f"{'deadline':>20}: {h['deadline']}")

    st = engine.stats()
    print("\n--- engine stats ---")
    for key in ("submitted", "served", "flushes", "panels_executed",
                "bucket_occupancy", "padding_waste", "exec_cache_hits",
                "exec_cache_misses", "requests_per_s"):
        val = st[key]
        print(f"{key:>20}: {val:.3f}" if isinstance(val, float)
              else f"{key:>20}: {val}")
    print("--- registry ---")
    for key, val in st["registry"].items():
        if key != "names":
            print(f"{key:>20}: {val}")

    # --- observability: Prometheus exposition + request-lifecycle trace
    expo = engine.metrics.exposition()
    print("\n--- metrics exposition (serve_* series) ---")
    for line in expo.splitlines():
        if line.startswith("serve_") and not line.endswith(" 0"):
            print(line)
    trace = tracer.to_chrome_trace()
    path = os.path.join(tempfile.gettempdir(), "serve_sparse_trace.json")
    with open(path, "w") as f:
        json.dump(trace, f)
        f.write("\n")
    admits = sum(e["name"] == "serve.admit" for e in trace["traceEvents"])
    completes = sum(
        e["name"] == "serve.complete" for e in trace["traceEvents"])
    flows = sum(e.get("cat") == "repro.flow"
                for e in trace["traceEvents"])
    print(f"\nwrote {len(trace['traceEvents'])}-event Perfetto trace "
          f"({admits} admits, {completes} completes, {flows} flow "
          f"events) to {path}")

    # --- the same metrics, scraped over HTTP: what a Prometheus
    #     scraper (or an on-call engineer with curl) sees
    with engine.serve_http() as srv:
        scraped = urllib.request.urlopen(
            f"{srv.url}/metrics", timeout=10).read().decode()
        health = json.loads(urllib.request.urlopen(
            f"{srv.url}/health", timeout=10).read().decode())
        explain = json.loads(urllib.request.urlopen(
            f"{srv.url}/explain/tenantB/fem", timeout=10).read().decode())
    served_line = next(line for line in scraped.splitlines()
                       if line.startswith("serve_served_total"))
    print(f"\nscraped {srv.url}/metrics: "
          f"{len(scraped.splitlines())} exposition lines "
          f"({served_line})")
    print(f"/health: deadline miss rate "
          f"{health['deadline']['miss_rate']:.2f}, "
          f"breakers {sorted(health['breakers'])}")
    print(f"/explain/tenantB/fem: tc_fraction "
          f"{explain['tc_fraction']:.2f}, "
          f"pipeline depth {explain['occupancy']['pipeline_depth']}")
    print("serve_sparse OK")


if __name__ == "__main__":
    main()
