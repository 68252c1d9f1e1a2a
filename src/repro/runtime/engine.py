"""DynamicGNNEngine — the paper's intelligent runtime around the GNN engine.

Wraps :class:`repro.core.gnn.GNNEngine` so the aggregation configuration
``(ps, dist, pb)`` can change *during* training without touching model
parameters: the training loop feeds each iteration's wall time into
:meth:`observe_step`; once a :class:`~repro.runtime.profiler.LatencyWindow`
fills, the reduced measurement goes to the
:class:`~repro.runtime.tuner.OnlineTuner`, and whenever the tuner moves to
a new candidate (or commits its final answer) the engine rebuilds the
aggregation plan — and, on the kernel path, the partition-blocked kernel —
for the new knobs.

Only the *engine* state is rebuilt.  Model parameters never move; what DOES
change with ``dist`` is the padded PGAS layout (``rows_per_dev`` is padded
to a multiple of ``dist``), so ``observe_step`` returns ``True`` when a
rebuild happened and the caller must re-pad node tables and re-jit its step
function (see examples/train_gnn.py's ``--dynamic-tune`` path).  Because
padded rows are masked out of both the loss and the aggregation, the loss
trajectory under any fixed config is bitwise identical to a static
:class:`GNNEngine` run with that config — the runtime machinery adds
measurement and plan swaps, never different math.

A :class:`~repro.runtime.cache.ConfigCache` (optional) warm-starts the
search from the config a previous run converged to for the same
workload-shape + hardware fingerprint, and receives the committed config
when this run's search closes.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.autotune import (HardwareSpec, WorkloadShape,
                                 hardware_spec, layer_workload_shapes)
from repro.core.gnn import GNNEngine
from repro.core.graph import CSRGraph
from repro.obs import NULL_TRACER
from repro.runtime.cache import ConfigCache
from repro.runtime.profiler import LatencyWindow, ProfileConfig
from repro.runtime.tuner import (DEFAULT_DIST, DEFAULT_PB, DEFAULT_PS,
                                 OnlineTuner, PerLayerTuner, make_vmem_check)

__all__ = ["DynamicGNNEngine"]


def _finite(obj):
    """JSON-safe copy: non-finite floats become None (Perfetto rejects
    the ``Infinity`` literal Python's json module would otherwise emit)."""
    if isinstance(obj, dict):
        return {k: _finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_finite(v) for v in obj]
    if isinstance(obj, float) and not np.isfinite(obj):
        return None
    return obj


def _as_config_dict(cfg) -> Dict:
    """Normalize a tuner proposal: a per-layer list becomes
    ``{"layers": [...]}`` so every config in histories/caches/logs is a
    plain dict."""
    if isinstance(cfg, list):
        return dict(layers=[dict(c) for c in cfg])
    return dict(cfg)


class DynamicGNNEngine:
    """A GNNEngine whose (ps, dist, pb) re-optimizes across iterations.

    Two tuning modes share one protocol:

    * **global** (an :class:`OnlineTuner`) — one (ps, dist, pb) for every
      layer; configs are ``{ps, dist, pb}`` dicts.
    * **per-layer** (a :class:`PerLayerTuner`, selected by passing
      ``layer_dims`` to :meth:`build`) — each layer runs its own plan over
      the shared partition; configs are ``{"layers": [{ps, dist, pb}, …]}``.
    """

    def __init__(
        self,
        graph: CSRGraph,
        mesh,
        *,
        tuner,
        shape: WorkloadShape,
        window: ProfileConfig = ProfileConfig(warmup=1, iters=3),
        cache: Optional[ConfigCache] = None,
        axis_name: str = "ring",
        interleave: bool = True,
        use_kernel: bool = False,
        self_loops: bool = True,
        fuse_update: bool = False,
        layer_dims: Optional[Sequence[int]] = None,
        hw: Optional[HardwareSpec] = None,
        log_fn: Callable[[str], None] = lambda _s: None,
        tracer=None,
        metrics=None,
    ):
        self.graph = graph
        self.mesh = mesh
        self.tuner = tuner
        # observability: tuner audit events flow through _on_audit into the
        # tracer (as tuner.* instants) and metrics registry.  NULL_TRACER's
        # recording calls are no-ops, so the default costs one branch.
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.metrics = metrics
        tuner.audit_sink = self._on_audit
        self.shape = shape
        self.cache = cache
        self.hw = hw if hw is not None else hardware_spec()
        self.axis_name = axis_name
        self.interleave = interleave
        self.use_kernel = use_kernel
        self.self_loops = self_loops
        self.fuse_update = fuse_update
        self.layer_dims = list(layer_dims) if layer_dims is not None else None
        self.log = log_fn
        self._window = LatencyWindow(window)
        self.step_count = 0
        self.committed = False
        self._layer_shapes: Optional[List[WorkloadShape]] = None
        # the MODEL's feature width as reported by the caller — in per-layer
        # mode self.shape holds the max aggregation width instead, so the
        # retune() unchanged-d_feat check needs this separately (build()
        # overwrites it with the true model width)
        self._model_d_feat = shape.d_feat
        self._partition = None   # SharedPartition, reused across tuner moves
        self.history: List[Tuple[int, Dict[str, int]]] = []
        cfg0 = tuner.propose()
        if cfg0 is None:  # empty search space ⇒ static engine at defaults
            cfg0 = dict(ps=DEFAULT_PS[0], dist=DEFAULT_DIST[0],
                        pb=DEFAULT_PB[0])
            if self.per_layer:
                cfg0 = [cfg0] * len(self.layer_dims)
            self.committed = True
        self._config = _as_config_dict(cfg0)
        self.engine = self._build_engine(self._config)
        self.history.append((0, dict(self._config)))

    @property
    def per_layer(self) -> bool:
        return self.layer_dims is not None

    # -- construction --------------------------------------------------------

    @classmethod
    def build(
        cls,
        graph: CSRGraph,
        mesh,
        *,
        d_feat: int,
        ps_space: Tuple[int, ...] = DEFAULT_PS,
        dist_space: Tuple[int, ...] = DEFAULT_DIST,
        pb_space: Tuple[int, ...] = DEFAULT_PB,
        cap_space: Tuple[int, ...] = (),
        k_space: Tuple[int, ...] = (),
        fanout_space: Tuple[int, ...] = (),
        batch_space: Tuple[int, ...] = (),
        tune_fuse: bool = False,
        window: ProfileConfig = ProfileConfig(warmup=1, iters=3),
        cache_path: Optional[str] = None,
        budget: Optional[int] = None,
        drift_threshold: float = 0.25,
        hw: Optional[HardwareSpec] = None,
        axis_name: str = "ring",
        interleave: bool = True,
        use_kernel: bool = False,
        self_loops: bool = True,
        fuse_update: bool = False,
        layer_dims: Optional[Sequence[int]] = None,
        log_fn: Callable[[str], None] = lambda _s: None,
        tracer=None,
        metrics=None,
    ) -> "DynamicGNNEngine":
        """``layer_dims`` (one aggregation feature width per layer, e.g.
        ``aggregation_widths(model, params)``) selects per-layer tuning:
        a :class:`PerLayerTuner` searches each layer's (ps, dist, pb) over
        one shared partition, warm-started from the global search.

        ``cap_space`` makes the tiered feature-cache capacity (rows held
        device-resident by :class:`repro.store.TieredFeatures`) a tuned
        knob — configs then carry a ``cap`` key, surfaced via
        :attr:`feature_capacity` for the storage layer to adopt.
        ``k_space`` makes the top-k compression width of the sparse ring
        payload (:func:`repro.core.pipeline.mgg_aggregate_sparse`) a tuned
        knob — configs then carry a ``k`` key, applied to hidden layers
        only (layer 0 stays dense; see :meth:`GNNEngine.stage_topk`).
        Offer only widths whose accuracy the caller has validated: the
        tuner's objective is latency, so it will take the narrowest
        candidate that measures fastest.
        ``fanout_space`` / ``batch_space`` make the sampled mini-batch
        geometry (:mod:`repro.sample`) tuned knobs — configs then carry
        ``fanout``/``batch`` keys, surfaced via :attr:`sample_fanout` /
        :attr:`sample_batch` for the sampling loop to adopt (they never
        reach the ring plans).  Same accuracy caveat as ``k_space``, and
        feed per-seed latencies (``dt / batch``) to ``observe_step`` if
        batch should optimize throughput (see :class:`OnlineTuner`).
        ``tune_fuse`` (per-layer mode only) probes flipping each layer's
        fused-update dataflow after its (ps, dist, pb) search settles;
        ``fuse_update`` remains the starting point for every layer."""
        if tune_fuse and layer_dims is None:
            raise ValueError(
                "tune_fuse probes a per-layer dataflow knob — pass "
                "layer_dims to select per-layer tuning")
        hw = hw if hw is not None else hardware_spec()
        n_dev = mesh.shape[axis_name]
        g = graph.with_self_loops() if self_loops else graph
        if not use_kernel:
            # pb only reaches the partition-blocked Pallas kernel; on the
            # jnp path every pb builds the identical computation, so probing
            # it would spend real training iterations measuring recompile
            # noise.  Collapse the dimension instead of searching it.
            pb_space = (min(pb_space),)
        cache = ConfigCache(cache_path) if cache_path else None
        if layer_dims is not None:
            shapes = layer_workload_shapes(g, n_dev, list(layer_dims))
            shape = max(shapes, key=lambda s: s.d_feat)
            warm = cache.get_layers(shapes) if cache is not None else None
            if warm is None and cache is not None:
                # a previous GLOBAL run's entry still seeds phase 1 — look
                # it up under the key global mode writes (d_feat, not the
                # aggregation width, which differs e.g. for unfused GCN)
                warm = cache.get(shapes[0].with_d_feat(int(d_feat)))
            warm = cls._clamp_pb(warm, pb_space)
            tuner = PerLayerTuner(
                len(shapes), ps_space, dist_space, pb_space,
                cap_space=cap_space, k_space=k_space,
                fanout_space=fanout_space, batch_space=batch_space,
                fuse_space=((fuse_update, not fuse_update) if tune_fuse
                            else (fuse_update,)),
                vmem_checks=[make_vmem_check(s, hw) for s in shapes],
                budget=budget, drift_threshold=drift_threshold,
                warm_start=warm,
            )
            tuner.observe_shape(shapes)
        else:
            shape = WorkloadShape.from_graph(g, n_dev, int(d_feat))
            warm = cache.get(shape) if cache is not None else None
            warm = cls._clamp_pb(warm, pb_space)
            tuner = OnlineTuner(
                ps_space, dist_space, pb_space, cap_space=cap_space,
                k_space=k_space,
                fanout_space=fanout_space, batch_space=batch_space,
                vmem_check=make_vmem_check(shape, hw),
                budget=budget, drift_threshold=drift_threshold,
                warm_start=warm,
            )
            tuner.observe_shape(shape)
        if warm is not None:
            log_fn(f"[runtime] warm start from cache: {warm}")
        eng = cls(graph, mesh, tuner=tuner, shape=shape, window=window,
                  cache=cache, axis_name=axis_name, interleave=interleave,
                  use_kernel=use_kernel, self_loops=self_loops,
                  fuse_update=fuse_update, layer_dims=layer_dims, hw=hw,
                  log_fn=log_fn, tracer=tracer, metrics=metrics)
        if layer_dims is not None:
            eng._layer_shapes = shapes
        eng._model_d_feat = int(d_feat)
        return eng

    @staticmethod
    def _clamp_pb(warm, pb_space):
        """Cached pb values outside the live space fall back to its floor."""
        if warm is None:
            return None
        if isinstance(warm, list):
            return [dict(c, pb=c["pb"] if c["pb"] in pb_space else pb_space[0])
                    for c in warm]
        if warm["pb"] not in pb_space:
            warm = dict(warm, pb=pb_space[0])
        return warm

    def _build_engine(self, cfg: Dict) -> GNNEngine:
        def _lc(c):
            # "cap" (storage layer — see feature_capacity) and
            # "fanout"/"batch" (sampling loop — see sample_fanout /
            # sample_batch) never reach the plan; "fuse" selects the
            # layer's dataflow; "k" is the sparse-payload width
            # (0/absent ⇒ dense ring).
            lc = dict(ps=int(c["ps"]), dist=int(c["dist"]),
                      pb=int(c["pb"]) if self.use_kernel else None)
            if "fuse" in c:
                lc["fuse_update"] = bool(c["fuse"])
            if c.get("k"):
                lc["topk"] = int(c["k"])
            return lc

        # The node split + locality split depend only on (graph, n_dev):
        # build them once and re-derive only the schedules on tuner moves
        # (invalidated in retune() when the topology changes).
        if "layers" in cfg:
            eng = GNNEngine.build(
                self.graph, self.mesh, axis_name=self.axis_name,
                layer_configs=[_lc(c) for c in cfg["layers"]],
                interleave=self.interleave, use_kernel=self.use_kernel,
                self_loops=self.self_loops, fuse_update=self.fuse_update,
                partition=self._partition, metrics=self.metrics,
            )
        else:
            eng = GNNEngine.build(
                self.graph, self.mesh, axis_name=self.axis_name,
                ps=int(cfg["ps"]), dist=int(cfg["dist"]),
                pb=int(cfg["pb"]) if self.use_kernel else None,
                topk=int(cfg["k"]) if cfg.get("k") else None,
                interleave=self.interleave, use_kernel=self.use_kernel,
                self_loops=self.self_loops, fuse_update=self.fuse_update,
                partition=self._partition, metrics=self.metrics,
            )
        self._partition = eng.partition
        return eng

    # -- GNNEngine surface (delegation: models take either engine) -----------

    @property
    def plan(self):
        return self.engine.plan

    @property
    def layer_plans(self):
        return self.engine.layer_plans

    def layer_plan(self, layer: int):
        return self.engine.layer_plan(layer)

    @property
    def layer_configs(self) -> List[Dict[str, int]]:
        return self.engine.layer_configs

    @property
    def deg(self):
        return self.engine.deg

    @property
    def config(self) -> Dict:
        return dict(self._config)

    def _global_knob(self, key: str) -> Optional[int]:
        """A globally-pinned optional knob's live value (per-layer configs
        pin one value across layers, so the first carrier is THE value)."""
        cfg = self._config
        if "layers" in cfg:
            for c in cfg["layers"]:
                if key in c:
                    return int(c[key])
            return None
        return int(cfg[key]) if key in cfg else None

    @property
    def feature_capacity(self) -> Optional[int]:
        """The live config's tiered-cache capacity (``cap`` knob), or
        None when capacity is not being tuned.  Per-layer configs pin one
        cap across layers (the feature table is shared), so the first
        layer's value is THE value."""
        return self._global_knob("cap")

    @property
    def sample_fanout(self) -> Optional[int]:
        """The live config's sampled-path per-hop neighbor bound
        (``fanout`` knob), or None when sampling is not being tuned.
        Global like ``cap`` — one block pipeline feeds every layer."""
        return self._global_knob("fanout")

    @property
    def sample_batch(self) -> Optional[int]:
        """The live config's sampled-path seed-batch size (``batch``
        knob), or None when sampling is not being tuned."""
        return self._global_knob("batch")

    def pad(self, x: np.ndarray) -> np.ndarray:
        return self.engine.pad(x)

    def shard(self, x):
        return self.engine.shard(x)

    def aggregate(self, x, layer: int = 0, update_w=None, topk=None):
        return self.engine.aggregate(x, layer=layer, update_w=update_w,
                                     topk=topk)

    def aggregate_update(self, x, w, layer: int = 0, topk=None):
        return self.engine.aggregate_update(x, w, layer=layer, topk=topk)

    def aggregate_streamed(self, tiered, layer: int = 0, update_w=None,
                           topk=None, stats=None, tracer=None):
        return self.engine.aggregate_streamed(
            tiered, layer=layer, update_w=update_w, topk=topk, stats=stats,
            tracer=tracer if tracer is not None else self.tracer)

    def stage_topk(self, layer: int):
        return self.engine.stage_topk(layer)

    def gcn_norm_aggregate(self, x, layer: int = 0, topk=None):
        return self.engine.gcn_norm_aggregate(x, layer=layer, topk=topk)

    def gcn_norm_aggregate_update(self, x, w, layer: int = 0, topk=None):
        return self.engine.gcn_norm_aggregate_update(x, w, layer=layer,
                                                     topk=topk)

    def mean_aggregate(self, x, layer: int = 0, topk=None):
        return self.engine.mean_aggregate(x, layer=layer, topk=topk)

    def mean_aggregate_update(self, x, w, layer: int = 0, topk=None):
        return self.engine.mean_aggregate_update(x, w, layer=layer, topk=topk)

    # -- observability -------------------------------------------------------

    @property
    def audit(self) -> List[dict]:
        """The tuner's audit trail (probe/reopen/retreat/adopt/commit
        events) — the machine-readable answer to "why this config"."""
        return self.tuner.audit

    def _on_audit(self, ev: dict) -> None:
        safe = _finite(ev)
        self.tracer.instant("tuner." + ev["event"], cat="tuner", **safe)
        if self.metrics is not None:
            self.metrics.counter("tuner.events", event=ev["event"]).inc()
            if ev["event"] == "probe":
                # model-vs-measured relative error for every probed config:
                # the continuous check that the §4 analytical model still
                # ranks configs the way this machine measures them (numpy
                # only — no device work on the audit path)
                err = self._model_error(ev)
                if err is not None:
                    self.metrics.histogram("tuner.model_error").observe(err)

    def _model_error(self, ev: dict) -> Optional[float]:
        cfg = ev.get("config") or ev.get("configs")
        lat = ev.get("latency")
        if cfg is None or lat is None or not np.isfinite(lat) or lat <= 0:
            return None
        from repro.obs.calibrate import model_latency
        shapes = self._layer_shapes if isinstance(cfg, list) else self.shape
        if shapes is None:
            return None
        try:
            model = model_latency(shapes, cfg, self.hw,
                                  interleave=self.interleave)
        except Exception:
            return None
        return abs(model - float(lat)) / float(lat)

    def calibrate(self, *, params=None, adopt: bool = True):
        """Fit ``self.hw`` to the latencies the search actually measured.

        Runs :func:`repro.obs.calibrate.fit_spec` over the audit trail's
        probe observations; with ``adopt=True`` (default) the calibrated
        spec replaces ``self.hw``, so subsequent re-tunes build their VMEM
        feasibility checks and model-error baselines against measured
        hardware constants instead of the shipped ones.  Returns the
        :class:`~repro.obs.calibrate.CalibrationResult` (None when the
        trail holds no usable measurements yet).
        """
        from repro.obs import calibrate as cal

        obs = self.tuner.observations()
        shapes = self._layer_shapes if self.per_layer else self.shape
        if shapes is None:
            return None
        kw = {} if params is None else {"params": params}
        result = cal.fit_spec(shapes, obs, self.hw,
                              interleave=self.interleave, **kw)
        if result is None:
            return None
        if self.metrics is not None:
            self.metrics.gauge("tuner.calibration_error").set(result.error)
            self.metrics.gauge("tuner.calibration_error_base") \
                .set(result.base_error)
        self.tracer.instant("tuner.calibrate", cat="tuner",
                            error=result.error, base_error=result.base_error,
                            n=result.n_observations)
        self.log(f"[runtime] {result.summary()}")
        if adopt:
            self.hw = result.spec
        return result

    # -- the online tuning protocol ------------------------------------------

    def observe_step(self, dt: float) -> bool:
        """Feed one training iteration's wall time.

        Returns True when the engine was rebuilt for a new config — the
        caller must then re-pad its node tables (layout may have changed
        with ``dist``) and re-jit anything that closed over the engine.
        """
        self.step_count += 1
        if self.metrics is not None:
            self.metrics.histogram("runtime.step_seconds").observe(dt)
        if self.tuner.converged:
            return False
        self._window.add(dt)
        if not self._window.ready:
            return False
        latency = self._window.value()
        self._window.reset()
        self.tuner.observe(latency)
        nxt = self.tuner.propose()
        if self.tuner.converged:
            return self._commit()
        return self._set_config(_as_config_dict(nxt))

    def retune(self, graph: Optional[CSRGraph] = None,
               d_feat: Optional[int] = None, *,
               layer_dims: Optional[Sequence[int]] = None,
               force: bool = False, from_cache: bool = False) -> bool:
        """Drift entry point: the workload changed (graph grew, features
        resized).  Recomputes the WorkloadShape; if it drifted past the
        tuner's threshold the search re-opens (warm-started from the old
        best) and the engine rebuilds against the new graph.

        Per-layer engines report width changes via ``layer_dims`` (one
        aggregation width per layer — a single ``d_feat`` cannot describe
        them); passing a changed ``d_feat`` alone there is an error rather
        than a silently dropped drift signal.

        ``force=True`` re-opens the search even when the WorkloadShape is
        unchanged.  This is the *traffic*-drift path: a serving frontend
        (see repro.serve.gnn) observes request statistics the shape cannot
        see — hot-set rotations, burst load — and the measured latency
        surface under the new traffic is stale evidence either way, so the
        caller's drift signal overrides the shape comparison.

        ``from_cache=True`` (only meaningful with ``force``) warm-starts
        the re-opened search from the shared :class:`ConfigCache` entry in
        *adopt* mode: a sibling serving replica already re-searched under
        the same shift and committed its optimum, so this engine validates
        that config with a single measurement instead of re-exploring
        (falls back to the normal warm re-search on a cache miss).
        """
        if graph is not None:
            self.graph = graph
            self._partition = None   # topology changed: re-partition
        if self.per_layer and d_feat is not None \
                and int(d_feat) != self._model_d_feat and layer_dims is None:
            raise ValueError(
                "per-layer engine: report feature-width changes via "
                "retune(layer_dims=[...]) — a lone d_feat cannot describe "
                "per-layer aggregation widths")
        if d_feat is None:
            d_feat = self._model_d_feat if self.per_layer \
                else self.shape.d_feat
        self._model_d_feat = int(d_feat)
        if layer_dims is not None:
            if not self.per_layer:
                raise ValueError("layer_dims on a global-mode engine")
            self.layer_dims = list(layer_dims)
        g = (self.graph.with_self_loops() if self.self_loops else self.graph)
        n_dev = self.mesh.shape[self.axis_name]
        if self.per_layer:
            shapes = layer_workload_shapes(g, n_dev, self.layer_dims)
            shape = max(shapes, key=lambda s: s.d_feat)
            reopened = self.tuner.observe_shape(shapes)
        else:
            shapes = None
            shape = WorkloadShape.from_graph(g, n_dev, int(d_feat))
            reopened = self.tuner.observe_shape(shape)
        adopted = False
        if force and not reopened:
            warm = None
            if from_cache and self.cache is not None:
                warm = (self.cache.get_layers(shapes) if self.per_layer
                        else self.cache.get(shape))
                warm = self._clamp_pb(warm, self.tuner.pb_space)
            if warm is not None:
                self.tuner.reopen(warm_start=warm, mode="adopt",
                                  cause="cache_adopt")
                adopted = True
                self.log(f"[runtime] adopting shared-cache config: {warm}")
            else:
                self.tuner.reopen(cause="traffic_drift")
            reopened = True
        if reopened and self.per_layer and not adopted:
            # the layer count / per-layer widths may have moved: resize the
            # search and rebuild the VMEM feasibility predicates against the
            # LIVE shapes (stale checks would admit configs that spill)
            self.tuner.reconfigure(
                num_layers=len(shapes),
                vmem_checks=[make_vmem_check(s, self.hw) for s in shapes])
        if reopened:
            self.shape = shape
            self._layer_shapes = shapes
            self.committed = False
            self._window.reset()
            self.log(f"[runtime] workload drift → search re-opened "
                     f"(reopen #{self.tuner.reopens})")
            nxt = self.tuner.propose()
            if nxt is not None:
                self._set_config(_as_config_dict(nxt),
                                 force_rebuild=graph is not None)
        elif graph is not None:
            # same shape class, new topology: rebuild the plan in place
            self.engine = self._build_engine(self._config)
        return reopened

    # -- internals -----------------------------------------------------------

    def _commit(self) -> bool:
        best = self.tuner.best
        self.committed = True
        if best is None:  # nothing measurable (all configs vmem-rejected)
            return False
        if self.cache is not None:
            if self.per_layer and self._layer_shapes is not None:
                self.cache.put_layers(self._layer_shapes, best,
                                      self.tuner.best_latency)
            elif not self.per_layer:
                self.cache.put(self.shape, best, self.tuner.best_latency)
        self.log(f"[runtime] tuning converged after "
                 f"{self.tuner.measured} measurements: {best} "
                 f"({self.tuner.best_latency * 1e3:.2f} ms)")
        self.tuner._emit("commit", config=_as_config_dict(best),
                         latency=self.tuner.best_latency,
                         step=self.step_count)
        return self._set_config(_as_config_dict(best))

    def _set_config(self, cfg: Dict,
                    force_rebuild: bool = False) -> bool:
        if cfg == self._config and not force_rebuild:
            return False
        self._config = dict(cfg)
        self.engine = self._build_engine(self._config)
        self.history.append((self.step_count, dict(self._config)))
        self.log(f"[runtime] step {self.step_count}: config → {self._config}")
        return True
