"""The aggregation's backward: ``x̄ = Aᵀ ȳ`` as the ring over the
transposed plan (``GNNEngine.backward_plan``), against autodiff of a plain
``segment_sum`` over the edge list.

The four-device cases run this file as a script in a fresh process, which
gives XLA's CPU backend four virtual devices before JAX starts: the pytest
process itself keeps seeing one device.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
import repro.core as C
from repro.obs import MetricsRegistry

RTOL = 1e-5
N, D = 300, 8


def _csr(dst, src, n):
    """CSR of the edges ``src → dst`` (row = destination), deduplicated."""
    order = np.lexsort((src, dst))
    e = np.unique(np.stack([dst[order], src[order]], 1), axis=0)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(e[:, 0], minlength=n), out=indptr[1:])
    return C.CSRGraph(indptr, e[:, 1].astype(np.int32), n)


def _edges(g):
    return np.repeat(np.arange(g.num_nodes), g.degrees), g.indices


def _graph(kind):
    g = C.power_law(N, avg_degree=9.0, locality=0.3, seed=1)
    dst, src = _edges(g)
    if kind == "symmetric":
        return _csr(np.concatenate([dst, src]), np.concatenate([src, dst]), N)
    if kind == "sources_and_sinks":
        # no edge into every 7th node, none out of every 5th from 3
        keep = (dst % 7 != 0) & (src % 5 != 3)
        g = _csr(dst[keep], src[keep], N)
        assert (g.degrees == 0).any()
        assert (np.bincount(g.indices, minlength=N) == 0).any()
    return g


def _grads_match(eng, g):
    """jax.grad through the engine's aggregation vs through segment_sum."""
    dst, src = _edges(g)
    kx, kw = jax.random.split(jax.random.key(0))
    x = jax.random.normal(kx, (N, D))
    w = jax.random.normal(kw, (N, D))
    xp = eng.shard(eng.pad(np.asarray(x)))
    wp = eng.shard(eng.pad(np.asarray(w)))
    got = jax.jit(jax.grad(lambda z: (eng.aggregate(z) * wp).sum()))(xp)
    want = jax.grad(lambda z: (jax.ops.segment_sum(z[src], dst, N)
                               * w).sum())(x)
    got = C.unpad_embeddings(eng.plan, np.asarray(got))
    err = np.abs(got - np.asarray(want)).max()
    assert err <= RTOL * np.abs(want).max(), err


def _gcn_step_counts():
    """A GCN 2 x 16 training step differentiates its two aggregations
    through the transposed plan (its input width is over 16, so layer 0
    transforms first and aggregates a function of W1); a forward alone
    builds and counts nothing."""
    from repro.dist import flat_ring_mesh
    g = _graph("directed")
    reg = MetricsRegistry()
    eng = C.GNNEngine.build(g, flat_ring_mesh(len(jax.devices())), ps=4,
                            dist=2, metrics=reg)
    init, apply, kw = C.MODEL_ZOO["gcn"]
    params = init(jax.random.key(1), 24, 5, **kw)
    xp = eng.shard(eng.pad(np.ones((N, 24), np.float32)))
    yp = eng.shard(eng.pad(np.arange(N, dtype=np.int32) % 5))
    mp = eng.shard(eng.pad(np.ones(N, np.float32)))
    jax.jit(lambda p, x: apply(p, eng, x)).lower(params, xp)
    assert reg.snapshot()["counters"] == {} and eng._backward == {}
    loss = lambda p: C.masked_cross_entropy(apply(p, eng, xp), yp, mp)
    grads = jax.jit(jax.grad(loss))(params)
    assert all(np.isfinite(a).all() for a in jax.tree.leaves(grads))
    assert reg.snapshot()["counters"] == {
        "mgg.aggregate.vjp{path=transposed}": 2}


def check(case):
    from repro.dist import flat_ring_mesh
    if case == "gcn_step_counter":
        return _gcn_step_counts()
    kind, dist, interleave, kernel = CASES[case]
    g = _graph(kind)
    reg = MetricsRegistry()
    eng = C.GNNEngine.build(g, flat_ring_mesh(len(jax.devices())), ps=4,
                            dist=dist, interleave=interleave,
                            use_kernel=kernel, metrics=reg,
                            self_loops=kind != "sources_and_sinks")
    _grads_match(eng, eng.graph)
    path = "symmetric" if kind == "symmetric" else "transposed"
    assert reg.snapshot()["counters"] == {f"mgg.aggregate.vjp{{path={path}}}": 1}
    if kind == "symmetric":   # the forward plan itself; no plan is built
        assert eng.backward_plan(eng.plan) is eng.plan
        assert eng._backward == {"partition": None}


# case -> (graph, dist, interleave, use_kernel); "ring" cases run on four
# virtual devices
CASES = {
    "one_device": ("directed", 1, True, False),
    "one_device_kernel": ("directed", 2, True, True),
    "ring_dist1": ("directed", 1, True, False),
    "ring_dist2": ("directed", 2, True, False),
    "ring_dist1_no_interleave": ("directed", 1, False, False),
    "ring_dist2_no_interleave": ("directed", 2, False, False),
    "ring_sources_and_sinks": ("sources_and_sinks", 2, True, False),
    "ring_symmetric": ("symmetric", 2, True, False),
    "one_device_symmetric": ("symmetric", 1, True, False),
}


@pytest.mark.parametrize("case", sorted(CASES) + ["gcn_step_counter",
                                                  "ring_gcn_step_counter"])
def test_aggregation_backward_matches_segment_sum(case):
    if not case.startswith("ring"):
        return check(case)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, os.path.abspath(__file__),
                        case.replace("ring_gcn", "gcn")],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0 and "PASSED" in r.stdout, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"


if __name__ == "__main__":
    repro.force_host_devices(4)
    assert len(jax.devices()) == 4
    check(sys.argv[1])
    print("PASSED")
