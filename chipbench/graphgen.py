"""Seeded power-law graph generator for the benchmark's traffic.

The draw follows ``repro.core.graph.power_law`` (copied here so that the
yardstick does not move with the program): in-degrees from a truncated
Zipf(alpha), sources Zipf-popular over a random permutation of node ids,
a ``locality`` share of them drawn near the destination, duplicates
removed.  The degree law and the locality are the program's stand-ins; no
public graph states them.

Unlike ``power_law``, the in-degree sequence is fixed: it is drawn once
from ``DEGREE_SEED`` and scaled to exactly ``edges`` (the configuration's
mean degree times its nodes), each row capped at a quarter of the nodes.
Every seed gives the same node count, edge count and in-degree sequence;
the seed moves only which sources each node has.  The first draw is
Zipf-popular and local; rows that lose sources to duplicates are refilled
uniformly.  No row holds a self-edge or a source twice.
"""
from __future__ import annotations

import numpy as np

DEGREE_SEED = 0
_MAX_ROUNDS = 64


def _rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(int(seed) % 2**64)


def degree_sequence(nodes: int, edges: int, alpha: float) -> np.ndarray:
    """In-degrees: Zipf(alpha) draws from ``DEGREE_SEED``, scaled so that
    they sum to ``edges``, each at most ``nodes // 4``."""
    cap = nodes // 4
    if not nodes <= edges <= nodes * cap:
        raise ValueError(f"{edges} edges do not fit {nodes} nodes of in-"
                         f"degree 1..{cap}")
    raw = _rng(DEGREE_SEED).zipf(alpha, size=nodes).astype(np.float64)
    raw = np.minimum(raw, cap)
    scale = edges / raw.sum()
    for _ in range(_MAX_ROUNDS):       # water-fill up to the cap
        deg = np.clip(raw * scale, 1, cap)
        short = edges - deg.sum()
        free = raw[raw * scale < cap].sum()
        if short < 0.5 or free == 0:
            break
        scale += short / free
    out = np.floor(deg).astype(np.int64)
    # the remainder, one edge each to the rows with the largest fractions
    frac = np.where(out < cap, deg - out, -1.0)
    out[np.argsort(-frac, kind="stable")[:edges - out.sum()]] += 1
    return out


def _draw_sources(rng, dst, nodes: int, alpha: float, locality: float, pop):
    src = pop[rng.zipf(alpha, size=dst.shape[0]) % nodes]
    if locality > 0.0:
        local = rng.random(dst.shape[0]) < locality
        width = max(2, nodes // 64)
        offs = rng.integers(-width, width + 1, size=dst.shape[0])
        src = np.where(local, (dst + offs) % nodes, src)
    return src.astype(np.int64)


def _row_ranks(dst, rng):
    """Random rank of each edge within its destination row."""
    order = np.argsort((dst << 31) | rng.integers(0, 2**31, dst.shape[0]),
                       kind="stable")
    counts = np.bincount(dst)
    starts = np.cumsum(counts) - counts
    rank = np.empty(dst.shape[0], np.int64)
    rank[order] = np.arange(dst.shape[0]) - starts[dst[order]]
    return rank


def generate(nodes: int, edges: int, alpha: float, locality: float,
             seed: int):
    """CSR ``(indptr, indices)`` of in-edges (row = destination, sorted
    sources) with ``degree_sequence(nodes, edges, alpha)``."""
    want = degree_sequence(nodes, edges, alpha)
    rng = _rng(seed)
    pop = rng.permutation(nodes)
    key = np.zeros(0, np.int64)        # sorted dst * nodes + src
    for rnd in range(_MAX_ROUNDS):
        need = want - np.bincount(key // nodes, minlength=nodes)
        if not need.any():
            break
        if rnd == 0:
            rows = np.repeat(np.arange(nodes, dtype=np.int64), need)
            new = _draw_sources(rng, rows, nodes, alpha, locality, pop)
        else:
            rows = np.repeat(np.arange(nodes, dtype=np.int64), 2 * need)
            new = rng.integers(0, nodes, size=rows.shape[0])
        new = np.unique(rows * nodes + new)
        new = new[new // nodes != new % nodes]
        if key.shape[0]:
            pos = np.minimum(np.searchsorted(key, new), key.shape[0] - 1)
            new = new[key[pos] != new]
        rows = new // nodes
        new = new[_row_ranks(rows, rng) < need[rows]]
        key = np.concatenate([key, new])
        key.sort()
    else:
        raise RuntimeError("graph generator did not reach its degrees")
    indptr = np.zeros(nodes + 1, np.int64)
    np.cumsum(want, out=indptr[1:])
    return indptr, (key % nodes).astype(np.int32)
