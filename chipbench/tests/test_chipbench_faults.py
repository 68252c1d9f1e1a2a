"""A run with the timed path broken underneath it reports ``correct``
false: a step that leaves the state unchanged, half of the training nodes
left out of the loss, and an aggregation that drops a neighbour slot.  The
run skips only the look for a chip; the cell is cut to a CPU size."""
import time

import jax
import jax.numpy as jnp
import pytest

import repro.core.gnn as G
import repro.core.pipeline as PL
import repro.train.optimizer as O
from chipbench import harness
from chipbench.tests.tiny import tiny_cell

CELLS = ["gcn2x16-reddit.train"]


def _run(name):
    return harness.run_cell(name, 2**31 + 3, 0.2, False,
                            t_start=time.perf_counter(), require_chip=False,
                            cell=tiny_cell(name))


def state_unchanged(monkeypatch):
    monkeypatch.setattr(O, "adamw_update",
                        lambda grads, state, params, cfg: (params, state, {}))


def half_batch(monkeypatch):
    ce = G.masked_cross_entropy

    def half(logits, labels, mask):
        keep = (jnp.arange(mask.shape[0]) % 2 == 0).astype(mask.dtype)
        return ce(logits, labels, mask * keep)
    monkeypatch.setattr(G, "masked_cross_entropy", half)


def slot_dropped(monkeypatch):
    gs = PL._gather_sum

    def dropped(buf, nbrs, mask, *a, **k):
        return gs(buf, nbrs, mask.at[:, -1].set(False), *a, **k)
    monkeypatch.setattr(PL, "_gather_sum", dropped)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    r = _run(name)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   slot_dropped])
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    jax.clear_caches()
    r = _run(name)
    assert not r["correct"], r["checks"]
