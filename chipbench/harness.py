"""The benchmark's one general runner: a cell is found by its name in
BENCHMARK.json, its configuration, traffic and limits by theirs.

One run: graph from the traffic and the seed, engine plan, data and weights
on the device, compile, the checked first steps (the same compiled step and
state that the window then drives), a window of ``seconds``, the reference,
one JSON line.  See PERF.md for what each metric means.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import os
import shutil
import sys
import time
from typing import Dict, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
GIB = 2.0 ** 30
TRACE_DIR = os.path.join(ROOT, ".chipbench", "trace")   # the last traced run


class NoChip(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict
    per_layer: list
    end_to_end: list


def load_cell(name: str) -> Cell:
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    wl = {w["name"]: w for w in bench["workloads"]}
    if name not in wl:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = wl[name]
    cfg_file = {c["name"]: c["file"] for c in bench["configs"]}[w["config"]]
    mine = lambda ms: [m for m in ms if name in m.get("workloads", [name])]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_json(os.path.join(ROOT, cfg_file)),
        traffic=_json(os.path.join(HERE, "traffic", w["traffic"] + ".json")),
        limits=_json(os.path.join(HERE, "limits", name + ".json")),
        per_layer=mine(bench["per_layer"]),
        end_to_end=mine(bench["end_to_end"]))


def model_module(cfg: Dict):
    return importlib.import_module(f"chipbench.models.{cfg['model']}")


def seed_key(seed: int):
    import jax
    seed = int(seed) % 2**64
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def make_graph(cfg: Dict, traffic: Dict, seed: int):
    from chipbench import graphgen
    return graphgen.generate(cfg["nodes"], cfg["edges"], traffic["alpha"],
                             traffic["locality"], seed)


def _data(key, n: int, features: int, classes: int):
    """Features with class-dependent means, labels, a 60% train mask."""
    import jax
    import jax.numpy as jnp
    kl, kc, kx, km = jax.random.split(key, 4)
    y = jax.random.randint(kl, (n,), 0, classes, jnp.int32)
    centers = jax.random.normal(kc, (classes, features), jnp.float32)
    x = centers[y] + 0.5 * jax.random.normal(kx, (n, features), jnp.float32)
    mask = (jax.random.uniform(km, (n,)) < 0.6).astype(jnp.float32)
    return x, y, mask


def make_data(key, n: int, features: int, classes: int):
    """The data in node order, on the device (for the reference)."""
    import jax
    return jax.jit(_data, static_argnums=(1, 2, 3))(key, n, features,
                                                    classes)


def make_padded_data(key, cfg: Dict, bounds, rows_per_dev: int):
    """The same data in the engine's padded layout: device ``d`` holds
    nodes ``bounds[d]:bounds[d+1]`` in its first rows, zeros after.  Made
    in one jitted call, so the features are never held twice."""
    import jax
    import jax.numpy as jnp

    def lay(a):
        blocks = [jnp.pad(a[b0:b1], [(0, rows_per_dev - (b1 - b0))]
                          + [(0, 0)] * (a.ndim - 1))
                  for b0, b1 in zip(bounds[:-1], bounds[1:])]
        return blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks)

    @jax.jit
    def make(key):
        return tuple(lay(a) for a in _data(key, cfg["nodes"],
                                           cfg["features"], cfg["classes"]))
    return make(key)


def _keys(seed: int):
    import jax
    kd, kp = jax.random.split(seed_key(seed))
    return kd, kp


# ---------------------------------------------------------------------------
# the program under test: build, compile, first steps
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Program:
    compiled: object
    state: tuple            # (params, opt)
    inputs: tuple           # (xp, yp, mp)
    hlo: str
    plan_build_s: float
    compile_s: float


def build_program(cell: Cell, seed: int, indptr, indices) -> Program:
    import jax
    import repro.core as C
    from repro.core import gnn as G
    from repro.dist import flat_ring_mesh
    from repro.train import optimizer as O

    cfg = cell.config
    n = cfg["nodes"]
    t0 = time.perf_counter()
    g = C.CSRGraph(indptr, indices, n)
    eng = C.GNNEngine.build(g, flat_ring_mesh(cell.chips), **cfg["engine"])
    plan_build_s = time.perf_counter() - t0

    _, apply, _ = G.MODEL_ZOO[cfg["model"]]   # widths from the params
    kd, kp = _keys(seed)
    t0 = time.perf_counter()
    xp, yp, mp = (eng.shard(a) for a in make_padded_data(
        kd, cfg, [int(b) for b in eng.plan.bounds], eng.plan.rows_per_dev))
    params = jax.jit(lambda k: model_module(cfg).init_params(k, cfg))(kp)
    opt = O.adamw_init(params)
    jax.block_until_ready((xp, params, opt))
    log(f"[chipbench] data and weights {time.perf_counter() - t0:.3f} s; "
        f"memory {memory_stats(cell.chips)}")
    ocfg = O.AdamWConfig(**cfg["optimizer"])

    def step(params, opt, xp, yp, mp):
        loss, grads = jax.value_and_grad(lambda p: G.masked_cross_entropy(
            apply(p, eng, xp), yp, mp))(params)
        params, opt, _ = O.adamw_update(grads, opt, params, ocfg)
        return params, opt, loss

    t0 = time.perf_counter()
    compiled = jax.jit(step).lower(params, opt, xp, yp, mp).compile()
    compile_s = time.perf_counter() - t0
    log(f"[chipbench] compiled step: {compiled.memory_analysis()}")
    return Program(compiled=compiled, state=(params, opt),
                   inputs=(xp, yp, mp),
                   hlo=compiled.as_text(), plan_build_s=plan_build_s,
                   compile_s=compile_s)


def first_steps(prog: Program, cfg: Dict):
    """The checked steps, through the window's own call and feed; leaves
    the program's state after them in ``prog.state``."""
    import jax
    from chipbench import reference as R

    params0, opt = prog.state
    params, losses, first_grad = params0, [], None
    for i in range(R.CHECK_STEPS):
        params, opt, loss = prog.compiled(params, opt, *prog.inputs)
        losses.append(float(jax.block_until_ready(loss)))
        if i == 0:   # the clipped gradient, from Adam's first moment
            b1 = cfg["optimizer"]["b1"]
            first_grad = jax.tree.map(lambda m: m / (1 - b1), opt["m"])
    prog.state = (params, opt)
    return R.readings(losses, first_grad, params0, params)


def run_window(prog: Program, seconds: float, trace_dir: Optional[str]):
    import jax

    params, opt = prog.state
    annot = jax.profiler.TraceAnnotation
    losses = []
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    with annot("chipbench.window"):
        t0 = time.perf_counter()
        while True:
            with annot("chipbench.dispatch"):
                params, opt, loss = prog.compiled(params, opt, *prog.inputs)
            with annot("chipbench.wait"):
                loss.block_until_ready()
            losses.append(loss)
            if time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
        with annot("chipbench.loss_read"):
            losses = np.asarray(jax.device_get(losses), np.float64)
    if trace_dir:
        jax.profiler.stop_trace()
    prog.state = (params, opt)
    return window_s, losses


def memory_stats(chips: int):
    import jax
    return [d.memory_stats() for d in jax.devices()[:chips]]


def peak_memory_bytes(chips: int) -> Optional[int]:
    """Peak device memory of the fullest chip: the peak of the buffers in
    use plus the peak that compiled programs reserved for their
    temporaries (on a TPU the step's temporaries are reserved, not in
    use)."""
    stats = memory_stats(chips)
    if any(s is None for s in stats):
        return None
    return max(int(s["peak_bytes_in_use"]) + int(s.get("peak_bytes_reserved",
                                                       0)) for s in stats)


# ---------------------------------------------------------------------------
# the reference, after the program's state is freed
# ---------------------------------------------------------------------------

def reference_data(cell: Cell, seed: int, indptr, indices):
    import jax
    import jax.numpy as jnp
    cfg = cell.config
    n = cfg["nodes"]
    kd, kp = _keys(seed)
    x, y, mask = make_data(kd, n, cfg["features"], cfg["classes"])
    dst = np.repeat(np.arange(n, dtype=np.int32), np.diff(indptr))
    src = indices
    if model_module(cfg).SELF_LOOPS:
        src = np.concatenate([src, np.arange(n, dtype=np.int32)])
        dst = np.concatenate([dst, np.arange(n, dtype=np.int32)])
    params0 = jax.jit(lambda k: model_module(cfg).init_params(k, cfg))(kp)
    return params0, dict(x=x, y=y, mask=mask, src=jnp.asarray(src),
                         dst=jnp.asarray(dst), n=n)


def reference_readings(cell: Cell, seed: int, indptr, indices):
    from chipbench import reference as R
    params0, data = reference_data(cell, seed, indptr, indices)
    mod = model_module(cell.config)
    return R.train_readings(mod.reference_logits, params0, data,
                            cell.config["optimizer"])


def judge(numbers: Dict, limits: Dict, window_finite: bool = True):
    """Each number the cell's limits name, against its limit."""
    checks = {k: dict(value=numbers[k], limit=v["limit"])
              for k, v in limits["numbers"].items()}
    ok = window_finite and all(c["value"] <= c["limit"]
                               for c in checks.values())
    return ok, checks


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def device_info(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if require_chip and d0.platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {d0.platform!r})")
    if require_chip and len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)}")
    return dict(platform=d0.platform, kind=d0.device_kind, count=len(devs))


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True,
             cell: Optional[Cell] = None):
    """One run of a cell; returns the result line as a dict."""
    from chipbench import peaks, tracereduce
    from chipbench import reference as R

    import repro.core  # noqa: F401  (the system under test, first)
    cell = cell or load_cell(name)
    device = device_info(cell.chips, require_chip)
    cfg = cell.config
    t0 = time.perf_counter()
    log(f"[chipbench] process start to JAX's devices {t0 - t_start:.3f} s")
    indptr, indices = make_graph(cfg, cell.traffic, seed)
    log(f"[chipbench] graph {time.perf_counter() - t0:.3f} s")
    prog = build_program(cell, seed, indptr, indices)
    t0 = time.perf_counter()
    prog_read = first_steps(prog, cfg)
    log(f"[chipbench] checked steps {time.perf_counter() - t0:.3f} s; "
        f"memory {memory_stats(cell.chips)}")
    setup_s = time.perf_counter() - t_start
    log(f"[chipbench] {name} seed {seed}: set-up {setup_s:.3f} s (plan "
        f"{prog.plan_build_s:.3f} s, compile {prog.compile_s:.3f} s), "
        f"first losses {prog_read['losses']}")

    trace_dir = None
    if trace:
        trace_dir = TRACE_DIR
        shutil.rmtree(trace_dir, ignore_errors=True)
    window_s, losses = run_window(prog, seconds, trace_dir)
    steps = int(losses.shape[0])
    failed = int((~np.isfinite(losses)).sum())
    peak_bytes = peak_memory_bytes(cell.chips)
    device["memory_peak_bytes"] = peak_bytes
    log(f"[chipbench] window {window_s:.4f} s, {steps} steps, "
        f"{window_s / steps:.6f} s/step, last loss {losses[-1]:.6f}, peak "
        f"{peak_bytes} bytes")

    hlo = prog.hlo
    plan_build_s, compile_s = prog.plan_build_s, prog.compile_s
    del prog
    gc.collect()

    t0 = time.perf_counter()
    ref_read = reference_readings(cell, seed, indptr, indices)
    numbers = R.compare(prog_read, ref_read)
    correct, checks = judge(numbers, cell.limits, failed == 0)
    log(f"[chipbench] reference {time.perf_counter() - t0:.3f} s, losses "
        f"{ref_read['losses']}; {numbers['detail']}")

    if trace:
        red = tracereduce.reduce(
            tracereduce.events_from_xspace(_xplane(trace_dir)),
            tracereduce.hlo_agg_ops(hlo))
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        ctx = dict(config=cfg, steps=steps, window_s=window_s, trace=red,
                   work=model_module(cfg).work_per_step(
                       cfg, cfg["edges"] + cfg["nodes"]),
                   peak=peaks.peak(device["kind"]), compile_s=compile_s,
                   plan_build_s=plan_build_s)
        metrics = {}
        for m in cell.per_layer:
            v = importlib.import_module(
                f"chipbench.metrics.{m['name']}").read(ctx)
            if v is not None:
                metrics[m["name"]] = dict(value=v, unit=m["unit"])
    else:
        e2e = dict(step_s=window_s / steps, setup_s=setup_s,
                   peak_hbm_gib=None if peak_bytes is None
                   else peak_bytes / GIB)
        metrics = {m["name"]: dict(value=e2e[m["name"]], unit=m["unit"])
                   for m in cell.end_to_end}
    result = dict(correct=bool(correct), attempted=steps, failed=failed,
                  metrics=metrics, device=device)
    if trace:
        result["breakdown"] = red["breakdown"]
    result["checks"] = checks
    return result


def _xplane(trace_dir: str) -> str:
    found = [os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
             for f in fs if f.endswith(".xplane.pb")]
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {found}")
    return found[0]
