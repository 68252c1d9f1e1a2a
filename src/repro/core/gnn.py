"""Full-graph GNN models on the MGG engine (paper §5 benchmarks).

Two models, with the paper's exact settings:

* **GCN** (Kipf & Welling) — 2 layers, 16 hidden dims:
  ``Z = softmax(Â · relu(Â X W¹) W²)`` with ``Â = D^{-1/2}(A+I)D^{-1/2}``.
* **GIN** (Xu et al.) — 5 layers, 64 hidden dims:
  ``h' = MLP((1+ε)h + Σ_{u∈N(v)} h_u)``.

plus GraphSAGE-mean as a third example model.  The sparse Â·X / Σ-neighbor
products run through :func:`repro.core.pipeline.mgg_aggregate`; the dense
``·W`` updates are plain (replicated-weight) matmuls (mirroring the paper's
use of cuBLAS for the update phase) — unless a layer's
:class:`~repro.core.placement.LayerPlan` sets ``fuse_update``, in which case
the update matmul runs *inside* the ring so its FLOPs overlap the next
tile's transfer.  Symmetric normalization is folded into per-node scalings
so the aggregation kernel stays a pure masked gather-sum.

Every model stage consumes its own LayerPlan (``engine.layer_plan(i)``):
layers can run different ``(ps, dist, pb, interleave)`` schedules over one
shared graph partition and PGAS layout.

Everything operates in the padded PGAS layout (placement.pad_embeddings);
``deg`` vectors are padded alongside.

The compiled step names its layers (``repro.obs.scopes``): the engine's
aggregations run under ``mgg.aggregate``, each stage's dense matmul, bias
and activation under ``gnn.update``, the loss under ``gnn.loss``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.obs.metrics import MetricsRegistry
from repro.obs.scopes import AGGREGATE, LOSS, UPDATE, scoped
from repro.obs.tracer import resolve

from .graph import CSRGraph
from .placement import (AggregationPlan, LayerPlan, SharedPartition,
                        build_layer_plans, build_partition, pad_embeddings,
                        pad_table, transposed_plan)
from .pipeline import (block_neighbor_sum, mgg_aggregate,
                       mgg_aggregate_sparse, mgg_aggregate_sparse_streamed,
                       mgg_aggregate_streamed)

__all__ = ["GNNEngine", "gcn_init", "gcn_apply", "gin_init", "gin_apply",
           "sage_init", "sage_apply", "gat_init", "gat_apply",
           "sage_apply_blocks", "apply_blocks", "BLOCK_MODELS",
           "masked_cross_entropy", "MODEL_ZOO", "aggregation_widths",
           "MODEL_STAGES", "num_stages", "apply_stage", "apply_from_stage"]


@dataclasses.dataclass
class GNNEngine:
    """Bundles graph partitioning state + the pipelined aggregation op.

    One engine per (graph, mesh, knob sets).  The engine holds one
    :class:`~repro.core.placement.LayerPlan` per GNN layer, all derived
    from a single shared graph partition: layers may run radically
    different ``(ps, dist, pb, interleave)`` schedules (GCN's wide input
    layer vs its 16-dim hidden layer want different knobs) while sharing
    one PGAS embedding layout, so activations flow between layers without
    re-padding.  A single-config engine is the degenerate case of one
    LayerPlan shared by every layer.

    ``aggregate`` is the Â-free neighbor sum; ``gcn_norm_aggregate``
    applies the symmetric normalization; the ``*_update`` variants run the
    layer's dense ``·W`` update fused into the ring (see
    pipeline.mgg_aggregate ``update_w``).

    The dense aggregation's backward runs the ring over the transposed
    graph (:meth:`backward_plan`); the fused-update and top-k paths keep
    autodiff's.  ``metrics`` (optional :class:`repro.obs.MetricsRegistry`)
    counts which backward each traced aggregation takes:
    ``mgg.aggregate.vjp{path=transposed|symmetric|autodiff}``.
    """

    layer_plans: List[LayerPlan]
    mesh: Mesh
    axis_name: str = "ring"
    use_kernel: bool = False
    deg: Optional[jax.Array] = None  # padded (N_pad,) float32, degree of A+I
    partition: Optional[SharedPartition] = None
    graph: Optional[CSRGraph] = None  # the aggregated graph (A+I)
    metrics: Optional[MetricsRegistry] = None
    # built at the first traced backward: "partition" -> the transposed
    # graph's partition (None on a symmetric graph); (ps, dist) -> its plan
    _backward: Dict = dataclasses.field(default_factory=dict, repr=False)

    @staticmethod
    def build(
        graph: CSRGraph,
        mesh: Mesh,
        *,
        axis_name: str = "ring",
        ps: int = 16,
        dist: int = 1,
        pb: Optional[int] = None,
        interleave: bool = True,
        use_kernel: bool = False,
        self_loops: bool = True,
        fuse_update: bool = False,
        topk: Optional[int] = None,
        layer_configs: Optional[Sequence[Dict]] = None,
        partition: Optional[SharedPartition] = None,
        tracer=None,
        metrics: Optional[MetricsRegistry] = None,
    ) -> "GNNEngine":
        """Build an engine; ``layer_configs`` (one ``{ps, dist, pb, ...}``
        dict per layer) selects per-layer plans, otherwise the single
        ``(ps, dist, pb)`` config is shared by every layer.  ``partition``
        reuses a previously built :class:`SharedPartition` (it must match
        this graph *after* self-loop handling and this mesh's device
        count) — the dynamic runtime passes it so tuner moves re-derive
        schedules without re-partitioning the graph.

        ``tracer`` (optional :class:`repro.obs.Tracer`) records an
        ``engine.build`` span with the children ``engine.partition`` and
        ``engine.layer_plans``; ``metrics`` receives the
        ``mgg.aggregate.vjp`` counter."""
        tracer = resolve(tracer)
        with tracer.span("engine.build", cat="engine"):
            g = graph.with_self_loops() if self_loops else graph
            n_dev = int(np.prod([mesh.shape[a] for a in mesh.axis_names])) \
                if axis_name == "__all__" else mesh.shape[axis_name]
            if layer_configs is None:
                layer_configs = [dict(ps=ps, dist=dist, pb=pb)]
            with tracer.span("engine.partition", cat="engine"):
                part = partition if partition is not None \
                    else build_partition(g, n_dev)
            with tracer.span("engine.layer_plans", cat="engine"):
                plans = build_layer_plans(g, n_dev, layer_configs,
                                          partition=part,
                                          interleave=interleave,
                                          fuse_update=fuse_update,
                                          topk=topk)
            plan0 = plans[0].plan
            deg = pad_table(plan0.bounds, plan0.rows_per_dev,
                            g.degrees.astype(np.float32)[:, None])[:, 0]
            return GNNEngine(
                layer_plans=plans, mesh=mesh, axis_name=axis_name,
                use_kernel=use_kernel,
                deg=jnp.asarray(np.maximum(deg, 1.0)),
                partition=part, graph=g, metrics=metrics,
            )

    # -- layer plan access ---------------------------------------------------

    def layer_plan(self, layer: int) -> LayerPlan:
        """The plan driving aggregation stage ``layer`` (clamped: stages
        beyond the configured depth reuse the last layer's plan — e.g.
        GIN's head dense, which never aggregates)."""
        return self.layer_plans[min(layer, len(self.layer_plans) - 1)]

    @property
    def num_layer_plans(self) -> int:
        return len(self.layer_plans)

    @property
    def per_layer(self) -> bool:
        return len(self.layer_plans) > 1

    @property
    def plan(self) -> AggregationPlan:
        """Layer 0's schedule; every layer shares its PGAS layout
        (``bounds`` / ``rows_per_dev``), so layout consumers (padding,
        pgas_rows, serving) can keep using this single handle."""
        return self.layer_plans[0].plan

    @property
    def interleave(self) -> bool:
        return self.layer_plans[0].interleave

    @property
    def pb(self) -> Optional[int]:
        return self.layer_plans[0].pb

    @property
    def config(self) -> Dict[str, int]:
        """Layer 0's (ps, dist, pb) — THE knob set for single-config
        engines; per-layer engines expose ``layer_configs``."""
        return self.layer_plans[0].config

    @property
    def layer_configs(self) -> List[Dict[str, int]]:
        return [lp.config for lp in self.layer_plans]

    # -- layout --------------------------------------------------------------

    def pad(self, x: np.ndarray) -> np.ndarray:
        return pad_embeddings(self.plan, x)

    def shard(self, x) -> jax.Array:
        spec = P(self.axis_name) if x.ndim == 1 else P(self.axis_name, None)
        return jax.device_put(x, NamedSharding(self.mesh, spec))

    def stage_topk(self, layer: int) -> Optional[int]:
        """Effective top-k compression for aggregation stage ``layer``:
        hidden layers only — layer 0's inputs aren't ours to sparsify, so
        the input layer always rides the dense ring."""
        return self.layer_plan(layer).topk if layer >= 1 else None

    # -- aggregation ---------------------------------------------------------

    def _count_vjp(self, path: str) -> None:
        if self.metrics is not None:
            self.metrics.counter("mgg.aggregate.vjp", path=path).inc()

    def backward_plan(self, plan: AggregationPlan) -> AggregationPlan:
        """The plan the backward of an aggregation over ``plan`` runs,
        ``x̄ = Aᵀ ȳ``: ``plan`` itself when the graph equals its transpose,
        else placement.transposed_plan.  Built when the first backward is
        traced and kept (serving and inference never build it); counted
        once per traced backward."""
        if "partition" not in self._backward:
            g_t = self.graph.transpose()
            self._backward["partition"] = None if _same_edges(self.graph, g_t) \
                else build_partition(g_t, plan.n_dev, plan.bounds)
        part_t = self._backward["partition"]
        if part_t is None:
            self._count_vjp("symmetric")
            return plan
        key = (plan.ps, plan.dist)
        if key not in self._backward:
            self._backward[key] = transposed_plan(part_t, plan)
        self._count_vjp("transposed")
        return self._backward[key]

    def aggregate(self, x: jax.Array, layer: int = 0,
                  update_w: Optional[jax.Array] = None,
                  topk: Optional[int] = None) -> jax.Array:
        lp = self.layer_plan(layer)
        if topk or update_w is not None:
            self._count_vjp("autodiff")
        if topk:
            return mgg_aggregate_sparse(
                x, lp.plan, self.mesh,
                k=int(topk),
                axis_name=self.axis_name,
                interleave=lp.interleave,
                use_kernel=self.use_kernel,
                pb=lp.pb,
                update_w=update_w,
            )
        return mgg_aggregate(
            x, lp.plan, self.mesh,
            axis_name=self.axis_name,
            interleave=lp.interleave,
            use_kernel=self.use_kernel,
            pb=lp.pb,
            update_w=update_w,
            transpose=lambda: self.backward_plan(lp.plan),
        )

    def aggregate_update(self, x: jax.Array, w: jax.Array, layer: int = 0,
                         topk: Optional[int] = None) -> jax.Array:
        """Fused ``(A x) @ W``: the update matmul runs inside the ring."""
        return self.aggregate(x, layer=layer, update_w=w, topk=topk)

    def aggregate_sparse(self, x: jax.Array, k: int, layer: int = 0,
                         update_w: Optional[jax.Array] = None) -> jax.Array:
        """Explicit-k sparse aggregation (``aggregate`` with ``topk=k``)."""
        return self.aggregate(x, layer=layer, update_w=update_w, topk=k)

    def aggregate_streamed(self, tiered, layer: int = 0,
                           update_w: Optional[jax.Array] = None,
                           topk: Optional[int] = None,
                           stats: Optional[Dict] = None,
                           tracer=None) -> jax.Array:
        """Partial-resident aggregation: chunks are pulled on demand from
        a :class:`repro.store.TieredFeatures` (host store + device hot
        cache), with each tile's host→device gather prefetched while the
        previous tile's ring is in flight — see
        :func:`repro.core.pipeline.mgg_aggregate_streamed`.  ``topk``
        additionally compresses each landed chunk so the in-flight rings
        carry the sparse payload (mgg_aggregate_sparse_streamed)."""
        lp = self.layer_plan(layer)
        if tiered.plan is not lp.plan:
            tiered.set_plan(lp.plan)
        if topk:
            return mgg_aggregate_sparse_streamed(
                tiered.chunk_fetcher(), lp.plan, self.mesh,
                k=int(topk),
                axis_name=self.axis_name,
                use_kernel=self.use_kernel,
                pb=lp.pb,
                update_w=update_w,
                stats=stats,
                tracer=tracer,
            )
        return mgg_aggregate_streamed(
            tiered.chunk_fetcher(), lp.plan, self.mesh,
            axis_name=self.axis_name,
            use_kernel=self.use_kernel,
            pb=lp.pb,
            update_w=update_w,
            stats=stats,
            tracer=tracer,
        )

    @scoped(AGGREGATE)
    def gcn_norm_aggregate(self, x: jax.Array, layer: int = 0,
                           topk: Optional[int] = None) -> jax.Array:
        """Â x with Â = D^{-1/2}(A+I)D^{-1/2} (self-loops already in plan)."""
        dinv = jax.lax.rsqrt(self.deg)[:, None].astype(x.dtype)
        return self.aggregate(x * dinv, layer=layer, topk=topk) * dinv

    @scoped(AGGREGATE)
    def gcn_norm_aggregate_update(self, x: jax.Array, w: jax.Array,
                                  layer: int = 0,
                                  topk: Optional[int] = None) -> jax.Array:
        """Fused ``(Â x) @ W``: the left diagonal scaling commutes with the
        right matmul, so ``D^{-1/2}((A (D^{-1/2} x)) W)`` is exact."""
        dinv = jax.lax.rsqrt(self.deg)[:, None].astype(x.dtype)
        return self.aggregate_update(x * dinv, w, layer=layer, topk=topk) \
            * dinv

    @scoped(AGGREGATE)
    def mean_aggregate(self, x: jax.Array, layer: int = 0,
                       topk: Optional[int] = None) -> jax.Array:
        return self.aggregate(x, layer=layer, topk=topk) \
            / self.deg[:, None].astype(x.dtype)

    @scoped(AGGREGATE)
    def mean_aggregate_update(self, x: jax.Array, w: jax.Array,
                              layer: int = 0,
                              topk: Optional[int] = None) -> jax.Array:
        """Fused ``(D^{-1} A x) @ W`` (same commutation as gcn_norm)."""
        return self.aggregate_update(x, w, layer=layer, topk=topk) \
            / self.deg[:, None].astype(x.dtype)


def _same_edges(g: CSRGraph, h: CSRGraph) -> bool:
    """Whether two graphs hold the same edges; ``h`` has sorted rows, as
    ``CSRGraph.transpose`` gives them."""
    if not np.array_equal(g.indptr, h.indptr):
        return False
    rows = np.repeat(np.arange(g.num_nodes), g.degrees)
    return np.array_equal(g.indices[np.lexsort((g.indices, rows))],
                          h.indices)


# ---------------------------------------------------------------------------
# parameter init / apply (no flax — plain pytrees, framework substrate)
# ---------------------------------------------------------------------------

def _dense_init(key, fan_in: int, fan_out: int, dtype=jnp.float32):
    w = jax.random.normal(key, (fan_in, fan_out), dtype) * jnp.sqrt(
        2.0 / (fan_in + fan_out)
    ).astype(dtype)
    return dict(w=w, b=jnp.zeros((fan_out,), dtype))


def _dense(p, x):
    return x @ p["w"] + p["b"]


def gcn_init(key, in_dim: int, num_classes: int, hidden: int = 16,
             num_layers: int = 2, dtype=jnp.float32) -> Dict:
    """Paper setting: 2 layers, 16 hidden dims."""
    dims = [in_dim] + [hidden] * (num_layers - 1) + [num_classes]
    keys = jax.random.split(key, num_layers)
    return dict(
        layers=[_dense_init(k, dims[i], dims[i + 1], dtype)
                for i, k in enumerate(keys)]
    )


def gcn_stage(params: Dict, engine: GNNEngine, h: jax.Array,
              i: int) -> jax.Array:
    """Layer ``i`` of the GCN: one aggregation + dense update (+ relu).

    Three dataflows, selected by the layer's plan: fused (update inside the
    ring — ``(Â h) W`` with per-tile partial matmuls), else
    update-before-aggregate when it shrinks the feature dim (D_in > D_out),
    else aggregate-first.  All three compute the same math (matmul
    associativity); MGG's kernel is agnostic to the order.
    """
    n = len(params["layers"])
    layer = params["layers"][i]
    d_in, d_out = layer["w"].shape
    tk = engine.stage_topk(i)  # hidden layers may ride the sparse ring
    if engine.layer_plan(i).fuse_update:
        h = engine.gcn_norm_aggregate_update(h, layer["w"], layer=i, topk=tk)
        with jax.named_scope(UPDATE):
            h = h + layer["b"]
    elif d_in >= d_out:
        # transform-first; bias after aggregation (PyG convention) so all
        # three dataflows compute identical math up to summation order
        with jax.named_scope(UPDATE):
            h = h @ layer["w"]
        h = engine.gcn_norm_aggregate(h, layer=i, topk=tk)
        with jax.named_scope(UPDATE):
            h = h + layer["b"]
    else:
        h = engine.gcn_norm_aggregate(h, layer=i, topk=tk)
        with jax.named_scope(UPDATE):
            h = _dense(layer, h)
    if i < n - 1:
        with jax.named_scope(UPDATE):
            h = jax.nn.relu(h)
    return h


def gcn_apply(params: Dict, engine: GNNEngine, x: jax.Array) -> jax.Array:
    """Z = Â relu(... Â relu(Â X W¹) ...) Wᴸ (logits; softmax in the loss)."""
    h = x
    for i in range(len(params["layers"])):
        h = gcn_stage(params, engine, h, i)
    return h


def gin_init(key, in_dim: int, num_classes: int, hidden: int = 64,
             num_layers: int = 5, dtype=jnp.float32) -> Dict:
    """Paper setting: 5 layers, 64 hidden dims; 2-layer MLP per GIN layer."""
    keys = jax.random.split(key, 2 * num_layers + 1)
    layers = []
    dims = [in_dim] + [hidden] * num_layers
    for i in range(num_layers):
        layers.append(dict(
            eps=jnp.zeros((), dtype),
            mlp1=_dense_init(keys[2 * i], dims[i], hidden, dtype),
            mlp2=_dense_init(keys[2 * i + 1], hidden, hidden, dtype),
        ))
    return dict(layers=layers,
                head=_dense_init(keys[-1], hidden, num_classes, dtype))


def gin_stage(params: Dict, engine: GNNEngine, h: jax.Array,
              i: int) -> jax.Array:
    """GIN stage ``i``: layers 0..L-1 are GIN layers, stage L is the head.

    Fused dataflow: ``((A h) + ε h) W₁ = (A h) W₁ + ε (h W₁)`` — the
    aggregate's ·W₁ runs inside the ring, the ε-scaled self term is a
    plain local matmul.
    """
    if i == len(params["layers"]):
        with jax.named_scope(UPDATE):
            return _dense(params["head"], h)
    layer = params["layers"][i]
    tk = engine.stage_topk(i)  # sparse ring for hidden layers; self term dense
    if engine.layer_plan(i).fuse_update:
        agg = engine.aggregate_update(h, layer["mlp1"]["w"], layer=i, topk=tk)
        with jax.named_scope(UPDATE):
            z = agg + layer["eps"] * (h @ layer["mlp1"]["w"]) \
                + layer["mlp1"]["b"]
            z = jax.nn.relu(z)
    else:
        agg = engine.aggregate(h, layer=i, topk=tk)  # Σ nbrs (+ self-loop)
        with jax.named_scope(UPDATE):
            z = agg + layer["eps"] * h  # (1+ε)h + Σ_{u∈N(v)}: self-loop → 1·h
            z = jax.nn.relu(_dense(layer["mlp1"], z))
    with jax.named_scope(UPDATE):
        return jax.nn.relu(_dense(layer["mlp2"], z))


def gin_apply(params: Dict, engine: GNNEngine, x: jax.Array) -> jax.Array:
    h = x
    for i in range(len(params["layers"]) + 1):
        h = gin_stage(params, engine, h, i)
    return h


def sage_init(key, in_dim: int, num_classes: int, hidden: int = 32,
              num_layers: int = 2, dtype=jnp.float32) -> Dict:
    dims = [in_dim] + [hidden] * (num_layers - 1) + [num_classes]
    keys = jax.random.split(key, 2 * num_layers)
    return dict(layers=[
        dict(self=_dense_init(keys[2 * i], dims[i], dims[i + 1], dtype),
             nbr=_dense_init(keys[2 * i + 1], dims[i], dims[i + 1], dtype))
        for i in range(num_layers)
    ])


def sage_stage(params: Dict, engine: GNNEngine, h: jax.Array,
               i: int) -> jax.Array:
    layer = params["layers"][i]
    tk = engine.stage_topk(i)  # sparse ring for hidden layers; self path dense
    if engine.layer_plan(i).fuse_update:
        agg = engine.mean_aggregate_update(h, layer["nbr"]["w"], layer=i,
                                           topk=tk)
        with jax.named_scope(UPDATE):
            nbr = agg + layer["nbr"]["b"]
    else:
        agg = engine.mean_aggregate(h, layer=i, topk=tk)
        with jax.named_scope(UPDATE):
            nbr = _dense(layer["nbr"], agg)
    with jax.named_scope(UPDATE):
        h = _dense(layer["self"], h) + nbr
        if i < len(params["layers"]) - 1:
            h = jax.nn.relu(h)
    return h


def sage_apply(params: Dict, engine: GNNEngine, x: jax.Array) -> jax.Array:
    h = x
    for i in range(len(params["layers"])):
        h = sage_stage(params, engine, h, i)
    return h


def sage_apply_blocks(params: Dict, h: jax.Array, blocks,
                      *, use_kernel: bool = False) -> jax.Array:
    """GraphSAGE-mean forward over sampled mini-batch blocks.

    ``h`` is the outermost block's source feature table — ``(num_src, D)``
    rows aligned with ``blocks[0]['nbr']``'s local indices, zeros in the
    ``-1``-padded slots (see ``TieredFeatures.gather_rows``).  ``blocks``
    is the jit-traced pytree from ``repro.sample.block_tree``, one entry
    per layer, outermost hop first.  Destination rows are the leading
    rows of each source table (dst-first ordering), so the self term is
    ``h[:num_dst]`` — no second gather.  Returns the ``(batch,
    num_classes)`` seed logits; rows of padded seeds are garbage and
    must stay masked in the loss (``masked_cross_entropy``).
    """
    layers = params["layers"]
    if len(blocks) != len(layers):
        raise ValueError(
            f"{len(blocks)} blocks for {len(layers)} layers — sample with "
            f"one fanout per layer")
    for i, (layer, blk) in enumerate(zip(layers, blocks)):
        nbr, mask = blk["nbr"], blk["mask"]
        s = block_neighbor_sum(h, nbr, mask, use_kernel=use_kernel)
        with jax.named_scope(AGGREGATE):
            deg = jnp.maximum(mask.sum(axis=-1), 1.0).astype(h.dtype)[:, None]
            s = s / deg
        with jax.named_scope(UPDATE):
            h = _dense(layer["self"], h[:nbr.shape[0]]) + _dense(
                layer["nbr"], s)
            if i < len(layers) - 1:
                h = jax.nn.relu(h)
    return h


# Block-capable models: the sampled mini-batch path is GraphSAGE-style
# by construction (per-hop fanout bound == per-layer neighbor sample).
BLOCK_MODELS = {"sage": sage_apply_blocks}


def apply_blocks(model: str, params: Dict, h: jax.Array, blocks,
                 *, use_kernel: bool = False) -> jax.Array:
    """Dispatch the sampled-block forward for ``model`` (see
    ``BLOCK_MODELS``; currently GraphSAGE only)."""
    if model not in BLOCK_MODELS:
        raise ValueError(
            f"model {model!r} has no sampled-block path (have: "
            f"{sorted(BLOCK_MODELS)})")
    return BLOCK_MODELS[model](params, h, blocks, use_kernel=use_kernel)


@scoped(LOSS)
def masked_cross_entropy(logits: jax.Array, labels: jax.Array,
                         mask: jax.Array) -> jax.Array:
    """Mean CE over real (non-padding) nodes; padded rows carry mask 0."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    m = mask.astype(jnp.float32)
    return -(ll * m).sum() / jnp.maximum(m.sum(), 1.0)


def gat_init(key, in_dim: int, num_classes: int, hidden: int = 32,
             num_layers: int = 2, heads: int = 4, dtype=jnp.float32) -> Dict:
    """GATv1 (Veličković et al.) — the paper cites it as GIN's successor.

    GATv1's softmax over (a_l·Wh_u + a_r·Wh_v) is source-decomposable (the
    destination term is constant per softmax and cancels), so each head is
    two engine sum-aggregations: Σ e^{s_u}·Wh_u and Σ e^{s_u}.
    """
    dims = [in_dim] + [hidden * heads] * (num_layers - 1) + [num_classes]
    keys = jax.random.split(key, 2 * num_layers)
    layers = []
    for i in range(num_layers):
        out_total = dims[i + 1]
        h = heads if i < num_layers - 1 else 1
        hd = out_total // h
        layers.append(dict(
            w=_dense_init(keys[2 * i], dims[i], out_total, dtype),
            a_l=(jax.random.normal(keys[2 * i + 1], (h, hd), dtype) * 0.1),
        ))
    return dict(layers=layers)


def gat_stage(params: Dict, engine: GNNEngine, h: jax.Array,
              i: int) -> jax.Array:
    # GAT's dense W is applied BEFORE aggregation (attention needs Wh per
    # source), so there is no post-aggregation update to fuse: the layer's
    # fuse_update flag is a no-op and fused == unfused bitwise.  topk is
    # likewise not honoured: zeroing entries of the e^s attention numerator
    # and denominator aggregations would bias the softmax, not sparsify it.
    # The dense transform and the attention scores are the update; the
    # weighted messages, their sums and the softmax division aggregate.
    layer = params["layers"][i]
    nh = layer["a_l"].shape[0]                 # heads (static)
    with jax.named_scope(UPDATE):
        z = _dense(layer["w"], h)              # (N, H·hd)
        npad, total = z.shape
        hd = total // nh
        zh = z.reshape(npad, nh, hd)
        s = jnp.einsum("nhd,hd->nh", zh, layer["a_l"])
        e = jnp.exp(jax.nn.leaky_relu(s, 0.2))  # source weights (N, H)
    with jax.named_scope(AGGREGATE):
        num = engine.aggregate((zh * e[..., None]).reshape(npad, total),
                               layer=i)
        den = engine.aggregate(jnp.repeat(e, hd, axis=1), layer=i)
        out = (num / jnp.maximum(den, 1e-9)).astype(h.dtype)
    if i < len(params["layers"]) - 1:
        with jax.named_scope(UPDATE):
            out = jax.nn.elu(out)
    return out


def gat_apply(params: Dict, engine: GNNEngine, x: jax.Array) -> jax.Array:
    h = x
    for i in range(len(params["layers"])):
        h = gat_stage(params, engine, h, i)
    return h


MODEL_ZOO = {
    "gcn": (gcn_init, gcn_apply, dict(hidden=16, num_layers=2)),
    "gin": (gin_init, gin_apply, dict(hidden=64, num_layers=5)),
    "sage": (sage_init, sage_apply, dict(hidden=32, num_layers=2)),
    "gat": (gat_init, gat_apply, dict(hidden=16, num_layers=2, heads=4)),
}


def aggregation_widths(model: str, params: Dict,
                       fused: bool = False) -> List[int]:
    """Feature width crossing the ring at each aggregation layer.

    This is the per-layer ``D`` the autotuner's latency model needs: GCN's
    input layer aggregates at a very different width than its 16-dim hidden
    layer, which is exactly why one global ``(ps, dist, pb)`` is the wrong
    shape.  ``fused`` widths reflect the fused dataflow (the ring carries
    the pre-update features).
    """
    widths: List[int] = []
    for layer in params["layers"]:
        if model == "gcn":
            d_in, d_out = layer["w"].shape
            widths.append(d_in if fused else min(d_in, d_out))
        elif model == "gin":
            widths.append(layer["mlp1"]["w"].shape[0])
        elif model == "sage":
            widths.append(layer["nbr"]["w"].shape[0])
        elif model == "gat":
            widths.append(layer["w"]["w"].shape[1])
        else:
            raise ValueError(f"unknown model {model!r}")
    return widths

# ---------------------------------------------------------------------------
# stage-wise access (the serving subsystem resumes inference from a cached
# layer-1 table; folding the SAME stage functions guarantees bitwise equality
# between the served logits and the offline *_apply full pass)
# ---------------------------------------------------------------------------

MODEL_STAGES = {
    "gcn": gcn_stage,
    "gin": gin_stage,
    "sage": sage_stage,
    "gat": gat_stage,
}


def num_stages(model: str, params: Dict) -> int:
    """Stages in ``model``'s forward pass (GIN's head dense is a stage)."""
    n = len(params["layers"])
    return n + 1 if model == "gin" else n


def apply_stage(model: str, params: Dict, engine: GNNEngine, h: jax.Array,
                i: int) -> jax.Array:
    return MODEL_STAGES[model](params, engine, h, i)


def apply_from_stage(model: str, params: Dict, engine: GNNEngine,
                     h: jax.Array, start: int) -> jax.Array:
    """Fold stages ``start..`` — ``apply_from_stage(m, p, e, x, 0)`` is the
    full forward, identical to ``MODEL_ZOO[m][1](p, e, x)``."""
    for i in range(start, num_stages(model, params)):
        h = apply_stage(model, params, engine, h, i)
    return h
