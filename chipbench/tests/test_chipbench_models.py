"""Each model's plain reference gives the program's logits on the same
weights and data, at a size a CPU test holds; and small guards of the
harness: the generator refuses degrees it cannot realize, a window with a
non-finite loss is not correct, and the half-batch fault the limits are
read against fails them."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import graphgen, harness, reference
from chipbench.tests.tiny import SIZES, tiny_cell

CELL = "gcn2x16-reddit.train"


def _cell(model):
    cell = tiny_cell(CELL)
    cfg = dict(cell.config, model=model, hidden=8,
               layers={"gcn": 2, "gin": 5}[model])
    return dataclasses.replace(cell, config=cfg)


@pytest.mark.parametrize("model", ["gcn", "gin"])
def test_reference_logits_match_the_program(model):
    import repro.core as C
    from repro.core import gnn as G
    from repro.dist import flat_ring_mesh

    cell = _cell(model)
    cfg, n, seed = cell.config, SIZES["nodes"], 2**31 + 21
    indptr, indices = harness.make_graph(cfg, cell.traffic, seed)
    eng = C.GNNEngine.build(C.CSRGraph(indptr, indices, n),
                            flat_ring_mesh(1), **cfg["engine"])
    params, data = harness.reference_data(cell, seed, indptr, indices)
    xp, _, _ = harness.make_padded_data(
        harness._keys(seed)[0], cfg, [int(b) for b in eng.plan.bounds],
        eng.plan.rows_per_dev)
    got = np.asarray(G.MODEL_ZOO[model][1](params, eng, eng.shard(xp)))[:n]
    mod = harness.model_module(cfg)
    want = np.asarray(mod.reference_logits(params, data["x"], data["src"],
                                           data["dst"], n))
    scale = np.abs(want).max()
    np.testing.assert_allclose(got / scale, want / scale, atol=1e-5)


def test_degree_sequence_refuses_what_it_cannot_realize():
    with pytest.raises(ValueError):
        graphgen.degree_sequence(100, 100 * 26, 2.1)   # cap is 25 a row
    with pytest.raises(ValueError):
        graphgen.degree_sequence(100, 99, 2.1)         # a row has none


def test_non_finite_window_is_not_correct():
    cell = harness.load_cell(CELL)
    zero = {k: 0.0 for k in cell.limits["numbers"]}
    assert harness.judge(zero, cell.limits)[0]
    assert not harness.judge(zero, cell.limits, window_finite=False)[0]


def test_half_batch_fault_is_not_correct():
    cell = tiny_cell(CELL)
    cfg, seed = cell.config, 17
    indptr, indices = harness.make_graph(cfg, cell.traffic, seed)
    params0, data = harness.reference_data(cell, seed, indptr, indices)
    logits = harness.model_module(cfg).reference_logits
    ref = reference.train_readings(logits, params0, data, cfg["optimizer"])
    keep = (jnp.arange(data["n"]) % 2 == 0).astype(data["mask"].dtype)
    got = reference.train_readings(logits, params0,
                                   dict(data, mask=data["mask"] * keep),
                                   cfg["optimizer"])
    assert not harness.judge(reference.compare(got, ref), cell.limits)[0]
