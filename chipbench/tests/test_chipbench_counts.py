"""Work counts of the models against hand counts, and the generator's
promise: every seed gives the same node count, edge count and in-degree
sequence, and different sources."""
import numpy as np
import pytest

from chipbench import graphgen, work
from chipbench.models import gcn, gin


def test_agg_bytes_hand_count():
    # 10 edges at width 3: 10 * (3 * 4 + 4) bytes read; 4 nodes * 3 * 4
    # bytes written
    assert work.agg_bytes(n=4, e=10, d=3) == 10 * 16 + 48


def test_gcn_work_hand_count():
    cfg = dict(nodes=5, features=7, hidden=3, classes=2, layers=2)
    e = 11   # edges with self-loops
    # layer 0 (7 -> 3, transform first, aggregates at 3): forward matmul
    # and weight gradient (X takes none); layer 1 (3 -> 2, transform first
    # at 2): forward, weight and input gradients; two aggregations a layer
    flops = 2 * (2 * 5 * 7 * 3) + 3 * (2 * 5 * 3 * 2) + 2 * 11 * 3 \
        + 2 * 11 * 2
    bytes_ = 2 * (11 * (3 * 4 + 4) + 5 * 3 * 4) \
        + 2 * (11 * (2 * 4 + 4) + 5 * 2 * 4)
    assert gcn.work_per_step(cfg, e) == dict(flops=flops, agg_bytes=bytes_)


def test_gin_work_hand_count():
    cfg = dict(nodes=5, features=7, hidden=3, classes=2, layers=2)
    e = 11
    mm = lambda k, m: 2 * 5 * k * m
    # every matmul three times (forward, weight and input gradient); layer
    # 0 aggregates X (width 7) once, layer 1 (width 3) forward and back
    flops = 3 * (mm(7, 3) + mm(3, 3) + mm(3, 3) + mm(3, 3) + mm(3, 2)) \
        + 11 * 7 + 2 * 11 * 3
    bytes_ = (11 * (7 * 4 + 4) + 5 * 7 * 4) \
        + 2 * (11 * (3 * 4 + 4) + 5 * 3 * 4)
    assert gin.work_per_step(cfg, e) == dict(flops=flops, agg_bytes=bytes_)


@pytest.mark.parametrize("nodes,deg,loc", [(2048, 48, 0.30),
                                           (1536, 64, 0.25)])
def test_generator_fixes_degrees_and_moves_sources(nodes, deg, loc):
    edges = nodes * deg
    want = graphgen.degree_sequence(nodes, edges, 2.1)
    graphs = [graphgen.generate(nodes, edges, 2.1, loc, seed)
              for seed in (0, 1, 2**31 + 5)]
    for indptr, indices in graphs:
        np.testing.assert_array_equal(np.diff(indptr), want)
        assert indices.shape[0] == edges
        assert indices.min() >= 0 and indices.max() < nodes
        rows = np.repeat(np.arange(nodes), want)
        assert not (rows == indices).any()                   # no self-edge
        pairs = rows.astype(np.int64) * nodes + indices
        assert np.unique(pairs).shape[0] == pairs.shape[0]   # no multi-edge
    a, b, c = (g[1] for g in graphs)
    assert (a != b).mean() > 0.5 and (b != c).mean() > 0.5


def test_generator_is_power_law_at_the_degree_seed():
    """The in-degrees sum to the edges, stay within 1 and a quarter of the
    nodes, and keep the Zipf draw's order and heavy tail."""
    nodes, edges = 20000, 20000 * 492
    deg = graphgen.degree_sequence(nodes, edges, 2.1)
    assert deg.sum() == edges
    assert deg.min() >= 1 and deg.max() == nodes // 4
    raw = np.random.default_rng(graphgen.DEGREE_SEED).zipf(2.1, size=nodes)
    assert (np.diff(deg[np.argsort(raw, kind="stable")]) >= -1).all()
    assert np.median(deg) < 0.5 * deg.mean()   # a heavy tail


def test_generator_same_seed_same_graph():
    a = graphgen.generate(1024, 1024 * 48, 2.1, 0.30, seed=77)
    b = graphgen.generate(1024, 1024 * 48, 2.1, 0.30, seed=77)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])


@pytest.mark.parametrize("n_dev", [1, 3])
def test_padded_data_is_the_engines_layout(n_dev):
    """The benchmark lays its data out as the engine pads embeddings."""
    import repro.core as C
    from chipbench import harness
    cfg = dict(nodes=300, features=6, classes=4)
    indptr, indices = graphgen.generate(300, 300 * 8, 2.1, 0.3, seed=4)
    g = C.CSRGraph(indptr, indices, 300).with_self_loops()
    plan = C.build_plan(g, n_dev, ps=4)
    key = harness.seed_key(11)
    x, y, mask = (np.asarray(a) for a in harness.make_data(key, 300, 6, 4))
    xp, yp, mp = harness.make_padded_data(
        key, cfg, [int(b) for b in plan.bounds], plan.rows_per_dev)
    np.testing.assert_array_equal(np.asarray(xp), C.pad_embeddings(plan, x))
    np.testing.assert_array_equal(np.asarray(yp),
                                  C.pad_embeddings(plan, y[:, None])[:, 0])
    np.testing.assert_array_equal(np.asarray(mp),
                                  C.pad_embeddings(plan, mask[:, None])[:, 0])
