"""Work that an aggregation requires, counted from shapes.

An aggregation of width ``d`` over ``e`` edges (self-loops included) into
``n`` nodes reads one source row and one index per edge and writes one
output row per node; its transpose in the backward pass does the same with
the cotangent.  The count is the same whatever implements the aggregation
(XLA gather, Pallas kernel, ring): it is what the algorithm needs, not
what an implementation moves.
"""
from __future__ import annotations

ELEM_BYTES = 4     # float32 rows
INDEX_BYTES = 4    # int32 source index


def agg_bytes(n: int, e: int, d: int) -> int:
    return e * (d * ELEM_BYTES + INDEX_BYTES) + n * d * ELEM_BYTES


def agg_flops(e: int, d: int) -> int:
    """One add per edge and column."""
    return e * d


def matmul_flops(n: int, k: int, m: int) -> int:
    return 2 * n * k * m
