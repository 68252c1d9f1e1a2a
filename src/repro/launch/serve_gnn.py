"""GNN serving launcher: Zipfian traffic with phase shifts over a
(dynamically re-tuned) MGG aggregation pipeline.

    PYTHONPATH=src python -m repro.launch.serve_gnn --dataset products \
        --model gcn --dynamic-tune --requests 200 --rotate --burst 4

Reports p50/p99 request latency per phase, the layer-1 cache hit rate,
and the retune trail (tuner audit events) when ``--dynamic-tune`` is on.
``--trace PATH`` writes a Chrome-trace JSON (request lifecycles, tuner
audit instants, and a streamed-pipeline profile pass with per-ring-step
spans and overlap efficiency — load it in ui.perfetto.dev);
``--metrics-json PATH`` writes the metrics snapshot plus the audit trail
machine-readably.  See docs/observability.md.
``--per-layer-tune`` re-optimizes one (ps, dist, pb) per GNN layer
(implies --dynamic-tune); ``--fuse-update`` serves with the dense ·W
update fused into the ring.

``--replicas N`` scales the engine out behind a router
(``--router {load,locality}``, see docs/cluster.md): N independent
serving replicas share one tuned-config cache (``--tune-cache`` or an
auto temp file), stagger their drift retunes through the cluster's
drain → retune → rejoin protocol, and never drop a request.
"""
import os
import sys

from repro import force_host_devices

# CPU device forcing must precede JAX's backend start-up
if "--devices" in sys.argv:
    force_host_devices(int(sys.argv[sys.argv.index("--devices") + 1]))
else:
    force_host_devices(8, if_unset=True)

import argparse
import tempfile

import numpy as np
import jax

import repro.core as C
from repro.dist import flat_ring_mesh
from repro.obs import MetricsRegistry, Tracer, merge_traces
from repro.runtime import DynamicGNNEngine, ProfileConfig
from repro.serve import (GNNServeEngine, ServeCluster, TrafficPhase,
                         WorkloadStats, ZipfTraffic, make_router, run_trace)


def _pct(lat, q):
    return float(np.percentile(np.asarray(lat), q)) if len(lat) else 0.0


def _print_audit(audit, indent="  "):
    """Human view of the tuner audit trail (the machine view goes to
    --metrics-json)."""
    for ev in audit:
        if ev["event"] == "probe":
            continue                       # one line per probe is too chatty
        detail = ", ".join(f"{k}={v}" for k, v in ev.items()
                           if k not in ("event", "measured"))
        print(f"{indent}[{ev['measured']:4d} measured] "
              f"{ev['event']}: {detail}")


def _dump_obs(args, tracer, registry, engines, replica_tracers=None):
    """Write --trace / --metrics-json.  ``engines`` are the serve engines
    whose dynamic runtimes contribute audit trails.  With per-replica
    tracers (``--replicas N --trace``) each replica's events are dumped
    as a JSONL sidecar and folded into ONE Perfetto timeline — the
    cluster (router/drain/rejoin) on its own process row, each replica
    on its own — via :func:`repro.obs.merge_traces`."""
    audits = {f"replica{i}": e.eng.audit
              for i, e in enumerate(engines) if e.dynamic}
    if args.metrics_json:
        registry.dump_json(args.metrics_json, extra={"audit": audits})
        print(f"[serve_gnn] metrics snapshot: {args.metrics_json}")
    if tracer is None or not args.trace:
        return
    if replica_tracers:
        paths, labels = [], []
        for label, t in [("cluster", tracer)] + [
                (f"replica{i}", rt)
                for i, rt in enumerate(replica_tracers)]:
            p = f"{args.trace}.{label}.jsonl"
            t.dump_jsonl(p)
            paths.append(p)
            labels.append(label)
        merge_traces(paths, labels, out=args.trace)
        n = len(tracer) + sum(len(t) for t in replica_tracers)
        print(f"[serve_gnn] merged chrome trace: {args.trace} "
              f"({n} events across {len(paths)} timelines — open in "
              f"ui.perfetto.dev; sidecars: {args.trace}.*.jsonl)")
    else:
        tracer.dump_chrome(args.trace)
        print(f"[serve_gnn] chrome trace: {args.trace} "
              f"({len(tracer)} events — open in ui.perfetto.dev)")


def _profile_pipeline(srv, tracer, passes=3):
    """Run a few streamed aggregations through the live tiered store so
    the trace carries ring-step spans (``mgg.stream.*``) with measured
    overlap efficiency.  Serving's full pass jits the whole forward, so
    per-ring-step host timing is only observable through this explicit
    streamed profile pass — values are identical (fixed-order sum), only
    the schedule is traced."""
    if srv.tiers is None:
        print("[serve_gnn] pipeline profile skipped "
              "(needs --feature-capacity for the tiered streamed path)")
        return
    stats = {}
    for _ in range(passes):
        out = srv.eng.aggregate_streamed(srv.tiers, stats=stats,
                                         tracer=tracer)
        jax.block_until_ready(out)
    print(f"[serve_gnn] pipeline profile: overlap efficiency "
          f"{stats.get('overlap_efficiency', 0.0):.3f} "
          f"(prefetch {stats.get('prefetch_inflight', 0)}/"
          f"{stats.get('prefetch_issued', 0)} in flight)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="products")
    ap.add_argument("--model", default="gcn",
                    choices=["gcn", "gin", "sage", "gat"])
    ap.add_argument("--scale", type=float, default=0.25)
    ap.add_argument("--requests", type=int, default=200,
                    help="requests per phase")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--alpha", type=float, default=1.1)
    ap.add_argument("--rate", type=float, default=200.0)
    ap.add_argument("--rotate", action="store_true",
                    help="rotate the hot set at the phase boundary")
    ap.add_argument("--burst", type=float, default=1.0,
                    help="phase-2 rate multiplier (burst load)")
    ap.add_argument("--update-frac", type=float, default=0.02)
    ap.add_argument("--dynamic-tune", action="store_true")
    ap.add_argument("--per-layer-tune", action="store_true",
                    help="one (ps, dist, pb) per GNN layer "
                         "(implies --dynamic-tune)")
    ap.add_argument("--fuse-update", action="store_true",
                    help="run the dense ·W update inside the ring")
    ap.add_argument("--no-cache", action="store_true")
    ap.add_argument("--feature-capacity", type=int, default=None,
                    help="serve tiered: features live in a host store, "
                         "the device holds only this many hot rows "
                         "(0 = stream everything)")
    ap.add_argument("--frontier-fanout", type=int, default=None,
                    help="bound the stats-side receptive field with a "
                         "sampled k-hop frontier of this per-hop fanout "
                         "(repro.sample); cache gating stays exact")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serving replicas behind the router")
    ap.add_argument("--router", default="locality",
                    choices=["load", "locality"],
                    help="cluster routing policy (--replicas > 1)")
    ap.add_argument("--tune-cache", default=None,
                    help="shared ConfigCache path (replicas warm-start "
                         "each other's retunes through it)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--check-every", type=int, default=8,
                    help="micro-batches between traffic-drift checks")
    ap.add_argument("--stats-window", type=int, default=32,
                    help="WorkloadStats window (smaller = drift-sensitive)")
    ap.add_argument("--min-records", type=int, default=8,
                    help="stats records required before drift checks")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome-trace JSON (open in "
                         "ui.perfetto.dev): request lifecycles, ring-step "
                         "pipeline spans, tuner audit events")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write the metrics-registry snapshot + tuner "
                         "audit trail as JSON")
    args = ap.parse_args()
    args.dynamic_tune = args.dynamic_tune or args.per_layer_tune

    tracer = Tracer() if args.trace else None
    registry = MetricsRegistry()

    g, meta = C.paper_dataset(args.dataset, scale=args.scale)
    dim = min(int(meta["dim"]), 64)
    ncls = min(int(meta["classes"]), 16)
    x = np.random.default_rng(args.seed).normal(
        size=(g.num_nodes, dim)).astype(np.float32)
    mesh = flat_ring_mesh(len(jax.devices()))

    init, _apply, kw = C.MODEL_ZOO[args.model]
    params = init(jax.random.key(args.seed), dim, ncls, **kw)

    cache_path = args.tune_cache
    if args.dynamic_tune and args.replicas > 1 and cache_path is None:
        # replicas must share ONE cache for cross-replica warm starts
        cache_path = os.path.join(
            tempfile.mkdtemp(prefix="mgg-serve-"), "tuned.json")
        print(f"[serve_gnn] shared config cache: {cache_path}")

    def build_replica(idx=0, rep_tracer=None):
        rtr = rep_tracer if rep_tracer is not None else tracer
        if args.dynamic_tune:
            layer_dims = C.aggregation_widths(args.model, params,
                                              fused=args.fuse_update) \
                if args.per_layer_tune else None
            eng = DynamicGNNEngine.build(
                g, mesh, d_feat=dim,
                ps_space=(1, 2, 4, 8, 16), dist_space=(1, 2, 4),
                pb_space=(1,),
                window=ProfileConfig(warmup=1, iters=2),
                fuse_update=args.fuse_update, layer_dims=layer_dims,
                cache_path=cache_path, log_fn=print,
                tracer=rtr, metrics=registry)
        else:
            eng = C.GNNEngine.build(g, mesh, ps=8, dist=1,
                                    fuse_update=args.fuse_update, tracer=rtr)
        labels = {"replica": idx} if args.replicas > 1 else {}
        return GNNServeEngine(eng, params, args.model, x, g,
                              slots=args.slots,
                              stats=WorkloadStats(window=args.stats_window),
                              check_every=args.check_every,
                              min_records=args.min_records,
                              use_cache=not args.no_cache,
                              feature_capacity=args.feature_capacity,
                              frontier_fanout=args.frontier_fanout,
                              frontier_seed=args.seed + idx,
                              log_fn=print, tracer=rtr,
                              metrics=registry, obs_labels=labels)

    phases = [
        TrafficPhase(requests=args.requests, alpha=args.alpha,
                     rate=args.rate, seeds_max=min(4, args.slots),
                     update_frac=args.update_frac),
        TrafficPhase(requests=args.requests, alpha=args.alpha,
                     rate=args.rate * args.burst, rotate=args.rotate,
                     seeds_max=min(4, args.slots),
                     update_frac=args.update_frac),
    ]
    traffic = ZipfTraffic(g.num_nodes, dim, phases, seed=args.seed)

    if args.replicas > 1:
        # each replica records onto its OWN tracer (pid = replica index +
        # 1; the cluster keeps pid 0) so the dump can merge N replica
        # timelines into one Perfetto view with distinct process rows
        rep_tracers = ([Tracer(pid=i + 1) for i in range(args.replicas)]
                       if tracer is not None else None)
        replicas = [build_replica(i, rep_tracers[i] if rep_tracers else None)
                    for i in range(args.replicas)]
        cluster = ServeCluster(replicas, router=make_router(args.router),
                               log_fn=print, tracer=tracer,
                               metrics=registry)
        results = cluster.run_trace(traffic)
        lat = [r.latency for r in results]
        rep = cluster.report()
        print(f"cluster: {rep['replicas']} replicas, "
              f"router={rep['router']}, served {rep['served']} "
              f"(dropped {rep['dropped']}, shadow {rep['shadow_served']})")
        print(f"latency p50 {_pct(lat, 50) * 1e3:.2f} ms  "
              f"p99 {_pct(lat, 99) * 1e3:.2f} ms")
        print(f"staggered retunes {rep['staggered_retunes']} "
              f"(deferred {rep['deferred_retunes']})")
        for entry in rep["retune_log"]:
            print(f"  {entry}")
        for i, p in enumerate(rep["per_replica"]):
            print(f"  replica {i}: served {p['served']}, hit rate "
                  f"{p['cache_hit_rate']:.3f}, retunes {p['retunes']}, "
                  f"config {p['config']}")
        if any(p.get("tiers") for p in rep["per_replica"]):
            print(f"tiered features (cluster): "
                  f"{rep['host_rows_streamed']} rows streamed from host, "
                  f"{rep['cache_rows_served']} rows served from device cache")
            for i, p in enumerate(rep["per_replica"]):
                t = p.get("tiers")
                if t:
                    print(f"  replica {i}: cap {t['capacity']} rows "
                          f"({t['resident_fraction']:.1%} resident), "
                          f"feature hit rate {t['hit_rate']:.3f}")
        if args.dynamic_tune:
            for i, r in enumerate(replicas):
                if r.dynamic and r.eng.audit:
                    print(f"  replica {i} audit trail:")
                    _print_audit(r.eng.audit, indent="    ")
        if tracer is not None:
            _profile_pipeline(replicas[0], rep_tracers[0])
        _dump_obs(args, tracer, registry, replicas,
                  replica_tracers=rep_tracers)
        return

    srv = build_replica()
    results = run_trace(srv, traffic)
    lat = [r.latency for r in results]
    rep = srv.report()
    print(f"served {rep['served']} requests over {rep['batches']} "
          f"micro-batches (dropped {rep['dropped']})")
    print(f"latency p50 {_pct(lat, 50) * 1e3:.2f} ms  "
          f"p99 {_pct(lat, 99) * 1e3:.2f} ms")
    print(f"cache hit rate {rep['cache_hit_rate']:.3f} "
          f"({rep['cache_stores']} stores, "
          f"{rep['cache_invalidations']} invalidations)")
    if rep["tiers"] is not None:
        t = rep["tiers"]
        print(f"tiered features: cap {t['capacity']} rows "
              f"({t['resident_fraction']:.1%} resident), feature hit rate "
              f"{t['hit_rate']:.3f}, streamed "
              f"{t['host_bytes_streamed'] / 1e6:.1f} MB from host")
    if args.dynamic_tune:
        print(f"retunes {rep['retunes']}, rebuilds {rep['rebuilds']}, "
              f"final config {rep['config']}")
        # retune trail, straight from the tuner audit events (the same
        # records --metrics-json captures machine-readably)
        _print_audit(srv.eng.audit)
    if tracer is not None:
        _profile_pipeline(srv, tracer)
    _dump_obs(args, tracer, registry, [srv])


if __name__ == "__main__":
    main()
