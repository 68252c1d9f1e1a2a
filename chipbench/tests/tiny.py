"""A cell of the benchmark cut to a size a CPU test runs in seconds: the
same model, traffic law, optimizer and limits, few nodes and features, and
a mean in-degree that so few nodes can hold."""
from __future__ import annotations

import dataclasses

from chipbench import harness

SIZES = {"nodes": 384, "features": 24, "classes": 5, "edges": 384 * 24}


def tiny_cell(name: str) -> harness.Cell:
    cell = harness.load_cell(name)
    return dataclasses.replace(cell, config=dict(cell.config, **SIZES))
