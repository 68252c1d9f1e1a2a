"""The control: the plain reference computed in the nearest precision
below the one the configuration states (``reference.CONTROL_DTYPE``), put
in the program's place.  It has to come out as not correct under each cell's
limits.  At a size a CPU test holds (the chip run of the same comparison,
at the cells' own sizes, is in PERF.md)."""
import pytest

from chipbench import harness, reference
from chipbench.tests.tiny import tiny_cell

CELLS = ["gcn2x16-reddit.train"]


def _readings(cell, seed, dtype):
    cfg = cell.config
    indptr, indices = harness.make_graph(cfg, cell.traffic, seed)
    params0, data = harness.reference_data(cell, seed, indptr, indices)
    return reference.train_readings(
        harness.model_module(cfg).reference_logits, params0, data,
        cfg["optimizer"], dtype=dtype)


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    cell = tiny_cell(name)
    for seed in (3, 2**31 + 9):
        numbers = reference.compare(
            _readings(cell, seed, reference.CONTROL_DTYPE),
            _readings(cell, seed, "float32"))
        ok, checks = harness.judge(numbers, cell.limits)
        assert not ok, checks
