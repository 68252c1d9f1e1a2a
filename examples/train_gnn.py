"""End-to-end full-graph GCN training on the MGG engine (paper §5 setting:
2-layer GCN, 16 hidden) over an 8-way ring, with checkpoint/restart and the
paper's §4 intelligent runtime:

    PYTHONPATH=src python examples/train_gnn.py [--steps 100] [--model gin]
        [--dynamic-tune] [--per-layer-tune] [--fuse-update] [--tune-fuse]
        [--tune-cache /tmp/mgg_tuned.json]

``--dynamic-tune`` wraps the engine in repro.runtime.DynamicGNNEngine:
every training iteration's wall time feeds the online ps → dist → wpb
search, and whenever the tuner moves, the aggregation plan is rebuilt and
the step re-jitted — model parameters never change, so the loss curve is
the same one the static engine would produce config-for-config.
``--per-layer-tune`` lifts the search to one config per GNN layer
(PerLayerTuner over the model's aggregation widths, warm-started from the
global optimum); ``--fuse-update`` runs each layer's dense ·W update
inside the ring (fused with the tile transfers).  ``--tune-cache``
persists the converged config(s) keyed by workload shape + hardware, so
the next run warm-starts from it.

``--metrics-json PATH`` writes the metrics registry: step times, the
tuner's counters, which backward each aggregation took
(``mgg.aggregate.vjp``), and JAX's compile events (``jax.compile.*``:
compiles and their seconds by phase, persistent-cache hits and misses).

``--sample-fanout F`` switches to the sampled mini-batch path
(GraphSAGE only): fanout-bounded k-hop blocks (repro.sample) over a
tiered feature store — the per-step working set is bounded by
``batch * (F + 1) ** layers`` rows regardless of graph size.
``--sample-batch`` sets the seed mini-batch size; with
``--dynamic-tune``, fanout and batch become tuner knobs (climbed over
{F, 2F} × {B, 2B} on per-seed step latency) and the loop adopts the
tuned values live.
"""
from repro import force_host_devices

force_host_devices(8, if_unset=True)   # CPU rehearsal ring; a TPU ignores it

import argparse
import tempfile
import time

import numpy as np
import jax
import jax.numpy as jnp

import repro.core as C
from repro.dist import flat_ring_mesh
from repro.obs import MetricsRegistry, Tracer, watch_compiles
from repro.runtime import DynamicGNNEngine, ProfileConfig
from repro.train.data import graph_features
from repro.train.optimizer import AdamWConfig, adamw_init, adamw_update
from repro.train import checkpoint as ck


def run_sampled(args, g, x, y, train_mask, dim, ncls, mesh,
                tracer, registry):
    """Sampled mini-batch GraphSAGE: fanout-bounded blocks over the
    tiered store.  Fixed-shape blocks ⇒ one jit compile per (fanout,
    batch); the dynamic tuner (when on) climbs exactly those two knobs
    on per-seed step latency and the loop adopts its moves live."""
    from repro.sample import block_tree, sample_blocks, seed_batches
    from repro.store import FeatureStore, TieredFeatures

    init, _, kw = C.MODEL_ZOO["sage"]
    params = init(jax.random.key(0), dim, ncls, **kw)
    n_layers = len(params["layers"])
    fanout, batch = args.sample_fanout, args.sample_batch

    eng = None
    if args.dynamic_tune:
        # schedule knobs pinned (the ring plan is idle here — blocks
        # aggregate locally); the search space is the sampling geometry
        eng = DynamicGNNEngine.build(
            g, mesh, d_feat=dim,
            ps_space=(8,), dist_space=(1,), pb_space=(1,),
            fanout_space=(fanout, 2 * fanout),
            batch_space=(batch, 2 * batch),
            window=ProfileConfig(warmup=1, iters=2),
            cache_path=args.tune_cache or None, log_fn=print,
            tracer=tracer, metrics=registry)
        fanout = eng.sample_fanout or fanout
        batch = eng.sample_batch or batch

    store = FeatureStore(x)
    cap = args.feature_capacity if args.feature_capacity >= 0 \
        else g.num_nodes // 8
    tiers = TieredFeatures(store, None, capacity=cap)
    if cap:
        # degree order ≈ the Zipfian head: hubs land in most samples
        tiers.admit(np.argsort(-np.diff(g.indptr))[:cap])

    opt = adamw_init(params)
    ocfg = AdamWConfig(lr=5e-3, warmup_steps=5, total_steps=args.steps,
                       weight_decay=0.0)

    @jax.jit
    def step(params, opt, h0, btree, yb, mb):
        def loss_fn(p):
            logits = C.apply_blocks("sage", p, h0, btree)
            return C.masked_cross_entropy(logits, yb, mb)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt, _m = adamw_update(grads, opt, params, ocfg)
        return params, opt, loss

    rng = np.random.default_rng(0)
    train_ids = np.nonzero(train_mask)[0]

    def minibatches():
        while True:   # resample EVERY epoch — new draw, new neighbors
            yield from seed_batches(train_ids, batch, rng=rng)

    batches = minibatches()
    for i in range(args.steps):
        seeds, valid = next(batches)
        t0 = time.perf_counter()
        blocks = sample_blocks(g, seeds, [fanout] * n_layers,
                               batch=batch, rng=rng)
        h0 = tiers.gather_rows(blocks[0].src_ids)
        yb = jnp.asarray(y[np.clip(seeds, 0, None)].astype(np.int32))
        params, opt, loss = step(params, opt, h0, block_tree(blocks),
                                 yb, jnp.asarray(valid))
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
        if tracer is not None:
            tracer.complete("train.sampled_step", t0, t0 + dt, cat="train",
                            args={"step": i, "fanout": fanout,
                                  "batch": batch})
        registry.histogram("train.step_seconds").observe(dt)
        if eng is not None and eng.observe_step(dt / batch):
            # per-seed latency drives the climb; adopt the tuned geometry
            # (a batch move re-jits by shape, params are untouched)
            fanout = eng.sample_fanout or fanout
            batch = eng.sample_batch or batch
            batches = minibatches()
        if i % 10 == 0:
            print(f"step {i:4d} loss {float(loss):.4f} "
                  f"(fanout {fanout}, batch {batch})")

    # sampled inference over the held-out nodes, same block machinery
    test_ids = np.nonzero(~train_mask)[0]
    correct = total = 0
    for seeds, valid in seed_batches(test_ids, batch, rng=rng,
                                     shuffle=False):
        blocks = sample_blocks(g, seeds, [fanout] * n_layers,
                               batch=batch, rng=rng)
        logits = C.apply_blocks("sage", params,
                                tiers.gather_rows(blocks[0].src_ids),
                                block_tree(blocks))
        pred = np.asarray(logits).argmax(-1)
        live = valid > 0
        correct += int((pred[live] == y[seeds[live]]).sum())
        total += int(live.sum())
    rep = tiers.report()
    print(f"final loss {float(loss):.4f}; "
          f"sampled test acc {correct / max(1, total):.3f}")
    print(f"tiered store: cap {rep['capacity']} rows, hit rate "
          f"{rep['hit_rate']:.3f}, "
          f"{rep['host_rows_streamed']} host rows streamed")
    if eng is not None:
        print(f"tuned config: {eng.config} after "
              f"{eng.tuner.measured} measurements")
    if args.metrics_json:
        audit = eng.audit if eng is not None else []
        registry.dump_json(args.metrics_json, extra={"audit": audit})
        print(f"metrics snapshot: {args.metrics_json}")
    if tracer is not None:
        tracer.dump_chrome(args.trace)
        print(f"chrome trace: {args.trace} ({len(tracer)} events "
              f"— open in ui.perfetto.dev)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--model", default="gcn",
                    choices=["gcn", "gin", "sage", "gat"])
    ap.add_argument("--dataset", default="products")
    ap.add_argument("--workdir", default="")
    ap.add_argument("--dynamic-tune", action="store_true",
                    help="online cross-iteration (ps, dist, pb) tuning")
    ap.add_argument("--per-layer-tune", action="store_true",
                    help="tune one (ps, dist, pb) per GNN layer "
                         "(implies --dynamic-tune)")
    ap.add_argument("--fuse-update", action="store_true",
                    help="run the dense ·W update inside the ring")
    ap.add_argument("--tune-fuse", action="store_true",
                    help="let the per-layer tuner probe flipping each "
                         "layer's fused-update dataflow (implies "
                         "--per-layer-tune)")
    ap.add_argument("--tune-cache", default="",
                    help="JSON path persisting tuned configs across runs")
    ap.add_argument("--sample-fanout", type=int, default=0,
                    help="train on fanout-bounded sampled mini-batch "
                         "blocks instead of the full graph (sage only; "
                         "0 = full-graph)")
    ap.add_argument("--sample-batch", type=int, default=128,
                    help="seed mini-batch size for --sample-fanout")
    ap.add_argument("--feature-capacity", type=int, default=-1,
                    help="device hot-cache rows for the sampled path's "
                         "tiered store (-1 = num_nodes // 8)")
    ap.add_argument("--trace", default="", metavar="PATH",
                    help="write a Chrome-trace JSON (per-step spans + "
                         "tuner audit events — open in ui.perfetto.dev)")
    ap.add_argument("--metrics-json", default="", metavar="PATH",
                    help="write the metrics snapshot + tuner audit trail")
    args = ap.parse_args()
    args.per_layer_tune = args.per_layer_tune or args.tune_fuse
    args.dynamic_tune = args.dynamic_tune or args.per_layer_tune

    tracer = Tracer() if args.trace else None
    registry = MetricsRegistry()
    # JAX's compile events (the re-jits after a tuner move included) land
    # in the registry as jax.compile.*, and so in --metrics-json
    with watch_compiles(registry):
        train(args, ap, tracer, registry)


def train(args, ap, tracer, registry):
    """The training run that main's arguments ask for."""
    g, meta = C.paper_dataset(args.dataset, scale=0.5)
    # demo-friendly label space (the full #Class makes a 100-step CPU demo
    # unconvincing; benchmarks/table5 runs the accuracy study properly)
    ncls = min(int(meta["classes"]), 10)
    dim = min(int(meta["dim"]), 64)
    x, y, train_mask = graph_features(g.num_nodes, dim, ncls, seed=0)

    mesh = flat_ring_mesh(len(jax.devices()))
    if args.sample_fanout:
        if args.model != "sage":
            ap.error("--sample-fanout requires --model sage "
                     "(block aggregation is GraphSAGE-only)")
        run_sampled(args, g, x, y, train_mask, dim, ncls, mesh,
                    tracer, registry)
        return
    init, apply, kw = C.MODEL_ZOO[args.model]
    params = init(jax.random.key(0), dim, ncls, **kw)

    if args.dynamic_tune:
        layer_dims = C.aggregation_widths(args.model, params,
                                          fused=args.fuse_update) \
            if args.per_layer_tune else None
        eng = DynamicGNNEngine.build(
            g, mesh, d_feat=dim,
            ps_space=(1, 2, 4, 8, 16, 32), dist_space=(1, 2, 4),
            pb_space=(1, 2, 4),
            window=ProfileConfig(warmup=1, iters=2),
            cache_path=args.tune_cache or None,
            fuse_update=args.fuse_update,
            tune_fuse=args.tune_fuse,
            layer_dims=layer_dims,
            log_fn=print,
            tracer=tracer, metrics=registry,
        )
    else:
        eng = C.GNNEngine.build(g, mesh, ps=16, dist=2,
                                fuse_update=args.fuse_update, tracer=tracer,
                                metrics=registry)
    opt = adamw_init(params)
    ocfg = AdamWConfig(lr=5e-3, warmup_steps=5, total_steps=args.steps,
                       weight_decay=0.0)

    def prepare():
        """Pad node tables for the CURRENT plan (layout changes with dist)."""
        pad1 = lambda a: C.pad_table(eng.plan.bounds, eng.plan.rows_per_dev,
                                     a[:, None])[:, 0]
        xp = eng.shard(eng.pad(x))
        yp = jnp.asarray(pad1(y.astype(np.int32)))
        mp = jnp.asarray(pad1(train_mask.astype(np.float32)))

        @jax.jit
        def step(params, opt):
            loss, grads = jax.value_and_grad(lambda p: C.masked_cross_entropy(
                apply(p, eng, xp), yp, mp))(params)
            params, opt, m = adamw_update(grads, opt, params, ocfg)
            return params, opt, loss

        return xp, step

    xp, step = prepare()
    workdir = args.workdir or tempfile.mkdtemp(prefix="gnn_ckpt_")
    for i in range(args.steps):
        t0 = time.perf_counter()
        params, opt, loss = step(params, opt)
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
        if tracer is not None:
            # the timing exists regardless — tracing just records it, so
            # the loss curve is bitwise-identical with tracing on or off
            tracer.complete("train.step", t0, t0 + dt, cat="train",
                            args={"step": i})
        registry.histogram("train.step_seconds").observe(dt)
        if args.dynamic_tune and eng.observe_step(dt):
            # tuner moved: the plan (and possibly the padded layout)
            # changed — re-pad and re-jit; params are untouched
            xp, step = prepare()
        if i % 10 == 0:
            print(f"step {i:4d} loss {float(loss):.4f}")
        if (i + 1) % 50 == 0:
            ck.save(workdir, i + 1, dict(params=params))
    logits = C.unpad_embeddings(eng.plan, np.asarray(apply(params, eng, xp)))
    pred = logits.argmax(-1)
    test = ~train_mask
    print(f"final loss {float(loss):.4f}; "
          f"test acc {(pred[test] == y[test]).mean():.3f}; "
          f"checkpoints in {workdir}")
    if args.dynamic_tune:
        print(f"tuned config: {eng.config} after "
              f"{eng.tuner.measured} measurements "
              f"({len(eng.history) - 1} swaps)")
    if args.metrics_json:
        audit = eng.audit if args.dynamic_tune else []
        registry.dump_json(args.metrics_json, extra={"audit": audit})
        print(f"metrics snapshot: {args.metrics_json}")
    if tracer is not None:
        tracer.dump_chrome(args.trace)
        print(f"chrome trace: {args.trace} ({len(tracer)} events "
              f"— open in ui.perfetto.dev)")


if __name__ == "__main__":
    main()
