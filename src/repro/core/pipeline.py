"""MGG pipelined aggregation: shard_map + ppermute ring, double-buffered.

This is the paper's pipeline-centric kernel (§3.3–§3.4) re-expressed for TPU
(DESIGN.md §2).  Per chip, neighbor aggregation is split into

* a **local** pass over the chip's own embedding shard (paper: local virtual
  graph, full-HBM-bandwidth), and
* ``(n-1) · dist`` **ring steps**: each step aggregates one remote tile that
  arrived over ICI while the *previous* step's compute was running.  The loop
  body issues ``ppermute`` (tile *k+1*) and the gather+reduce for tile *k*
  with no data dependence between them — exactly the independence XLA's
  latency-hiding scheduler needs to overlap the DMA with compute.  This is
  the paper's Fig. 7(b) (async GET double-buffering) at ring-tile granularity.

The *interleave* flag reproduces §3.3's workload interleaving: local neighbor
partitions are spread across ring steps so every step carries both
latency-bound (remote) and compute-bound (local) work; ``interleave=False``
is the paper's Fig. 9(b) baseline.

The *fused update* path (``update_w``) additionally folds the dense ``·W``
update phase into the ring: each step's partial aggregate performs its own
``(P, D) @ (D, D_out)`` matmul before the scatter-add, so the update GEMM's
FLOPs — which otherwise run as a separate kernel after the ring drains —
overlap the in-flight ppermute of the next tile (the MaxK-GNN-style fused
aggregation+update kernel shape, expressed at ring-tile granularity).

Three baselines used throughout benchmarks:

* :func:`bulk_aggregate` — all-gather the full embedding table, then a purely
  local aggregation (the DGCL/NCCL pattern; paper §2.1, Table 4).
* :func:`fetch_rows_aggregate` — gather an explicit row set first, aggregate
  second, with a ``page_rows`` granularity knob.  ``page_rows=1`` models the
  Direct-NVSHMEM baseline (exact rows, no overlap; Table 1); larger values
  model UVM's page-granular migration with its wasted bandwidth (§2.2).

The backward of ``y = A x`` is ``x̄ = Aᵀ ȳ``.  :func:`mgg_aggregate` without
``update_w`` computes it as the same ring over a plan of the transposed
graph (``transpose=``; ``GNNEngine`` always passes it): a gather-sum that
reads ``ȳ``, where autodiff would scatter-add every gathered row.  On a
symmetric graph that plan is the forward plan itself.  These keep
autodiff's backward: the fused ``update_w`` path, the top-k sparse paths,
the streamed paths, :func:`block_neighbor_sum` and the baselines.

Inside a jitted program every aggregation runs under the ``mgg.aggregate``
scope and each ring rotation under ``mgg.ring`` (``repro.obs.scopes``).
The streamed path's pieces are jitted on their own, so their bodies carry
the scope themselves.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.obs.scopes import AGGREGATE, RING, scoped

from .placement import AggregationPlan

__all__ = [
    "mgg_aggregate",
    "mgg_aggregate_sparse",
    "mgg_aggregate_streamed",
    "mgg_aggregate_sparse_streamed",
    "topk_activation",
    "topk_decompress",
    "wire_index_dtype",
    "block_neighbor_sum",
    "bulk_aggregate",
    "fetch_rows_aggregate",
    "plan_device_arrays",
    "reference_aggregate",
    "collective_bytes",
    "sparse_collective_bytes",
]


# ---------------------------------------------------------------------------
# inner gather + reduce (the hot spot; Pallas kernel or jnp)
# ---------------------------------------------------------------------------

def _gather_sum(buf: jax.Array, nbrs: jax.Array, mask: jax.Array,
                use_kernel: bool, acc_dtype,
                pb: Optional[int] = None) -> jax.Array:
    """``out[p] = sum_j mask[p, j] * buf[nbrs[p, j]]`` → (P, D).

    The paper's warp-level gather+reduce.  ``use_kernel`` routes to the
    Pallas TPU kernel (kernels/neighbor_agg.py); the jnp path is the oracle
    and the CPU execution path.  ``pb`` (the paper's wpb knob) selects the
    partition-blocked kernel variant; the jnp path ignores it.
    """
    if use_kernel:
        from repro.kernels import ops as kops

        return kops.neighbor_gather_sum(buf, nbrs, mask, acc_dtype=acc_dtype,
                                        pb=pb)
    g = jnp.take(buf, nbrs, axis=0)  # (P, ps, D)
    return jnp.sum(
        g.astype(acc_dtype) * mask[..., None].astype(acc_dtype), axis=1
    )


def _padded_gather_sum(buf: jax.Array, nbrs: jax.Array, use_kernel: bool,
                       acc_dtype, pb: Optional[int] = None) -> jax.Array:
    """:func:`_gather_sum` over a maskless plan (placement.transposed_plan),
    whose padding slots index row ``len(buf)``: a zero row appended here,
    so the sum needs no mask and the compiled program holds none.  The
    kernel derives its mask from the indices."""
    if use_kernel:
        mask = nbrs < buf.shape[0]
        return _gather_sum(buf, jnp.where(mask, nbrs, 0), mask, True,
                           acc_dtype, pb)
    buf = jnp.concatenate([buf, jnp.zeros((1, buf.shape[1]), buf.dtype)])
    g = jnp.take(buf, nbrs, axis=0, mode="clip")  # (P, ps, D)
    return jnp.sum(g.astype(acc_dtype), axis=1)


@scoped(AGGREGATE)
def block_neighbor_sum(h_src: jax.Array, nbr: jax.Array, mask: jax.Array, *,
                       use_kernel: bool = False,
                       acc_dtype=jnp.float32) -> jax.Array:
    """Masked neighbor sum over one sampled block → ``(num_dst, D)``.

    ``h_src`` is the block's source embedding table; ``nbr``/``mask``
    are the fixed-shape ``(num_dst, fanout)`` tables from
    ``repro.sample`` whose padding slots point at local row
    ``num_src`` — a zero sentinel row appended here — so the sampled
    path rides the exact same masked gather-sum primitive (Pallas
    ``neighbor_gather_sum`` or the jnp oracle) as the full-graph ring.
    """
    sentinel = jnp.zeros((1, h_src.shape[1]), h_src.dtype)
    buf = jnp.concatenate([h_src, sentinel], axis=0)
    return _gather_sum(buf, nbr, mask, use_kernel, acc_dtype).astype(
        h_src.dtype)


# ---------------------------------------------------------------------------
# top-k activation compression (MaxK-GNN direction)
# ---------------------------------------------------------------------------

def topk_activation(x: jax.Array, k: int):
    """Keep the ``k`` largest entries per row: ``x → (values, col_idx)``.

    The compressed form is CSR-style with a *fixed* shape ``(N, k)`` —
    ``values[n, s] = x[n, col_idx[n, s]]`` — so jit caches stay warm across
    steps regardless of which columns survive.  ``lax.top_k`` guarantees the
    ``k`` column ids of a row are distinct, which is what makes
    :func:`topk_decompress` an exact (bitwise) inverse at ``k == D`` and
    order-independent for any ``k``.
    """
    values, idx = lax.top_k(x, k)
    return values, idx.astype(jnp.int32)


def wire_index_dtype(d_feat: int):
    """Narrowest integer dtype that can address a column of width ``d_feat``.

    The column-id half of the compressed payload travels the ring in this
    dtype: int16 covers every realistic feature width and keeps the wire
    cost of a ``(value, idx)`` pair at 6 bytes instead of 8.
    """
    return jnp.int16 if d_feat <= np.iinfo(np.int16).max else jnp.int32


def topk_decompress(values: jax.Array, idx: jax.Array, d_feat: int) -> jax.Array:
    """Inverse of :func:`topk_activation`: ``(N, k) → (N, d_feat)`` dense.

    Each row's column ids are distinct (a top-k guarantee), so every output
    slot is written at most once: the scatter is deterministic, bitwise
    invariant to any permutation of the compressed columns, and — at
    ``k == d_feat`` — an exact identity.
    """
    rows = values.shape[0]
    out = jnp.zeros((rows, d_feat), values.dtype)
    rr = jnp.arange(rows, dtype=jnp.int32)[:, None]
    return out.at[rr, idx.astype(jnp.int32)].set(values)


def _sparse_gather_sum(values: jax.Array, idx: jax.Array, nbrs: jax.Array,
                       mask: jax.Array, d_feat: int, use_kernel: bool,
                       acc_dtype, pb: Optional[int] = None) -> jax.Array:
    """Sparse analogue of :func:`_gather_sum` over compressed rows.

    ``use_kernel`` routes to the sparse Pallas kernel, which reads only the
    ``k`` live ``(value, col)`` pairs per neighbor row (the MaxK-GNN
    co-design); the jnp path decompresses the buffer once and reuses the
    dense oracle, so at ``k == d_feat`` it is bitwise-equal to the dense
    pipeline.
    """
    if use_kernel:
        from repro.kernels import ops as kops

        return kops.sparse_neighbor_gather_sum(
            values, idx, nbrs, mask, d_feat=d_feat, acc_dtype=acc_dtype)
    return _gather_sum(topk_decompress(values, idx, d_feat), nbrs, mask,
                       False, acc_dtype, pb)


def plan_device_arrays(plan: AggregationPlan) -> Dict[str, np.ndarray]:
    """The device-resident pytree of an :class:`AggregationPlan` (a
    maskless plan ships no masks)."""
    arrays = dict(
        local_nbrs=plan.local_nbrs,
        local_mask=plan.local_mask,
        local_targets=plan.local_targets,
        remote_nbrs=plan.remote_nbrs,
        remote_mask=plan.remote_mask,
        remote_targets=plan.remote_targets,
    )
    return {k: v for k, v in arrays.items() if v is not None}


def _plan_specs(axis_name: str) -> Dict[str, P]:
    return {k: P(axis_name) for k in (
        "local_nbrs", "local_mask", "local_targets",
        "remote_nbrs", "remote_mask", "remote_targets")}


# ---------------------------------------------------------------------------
# MGG pipelined ring aggregation
# ---------------------------------------------------------------------------

def _rotate(x: jax.Array, axis_name: str, perm) -> jax.Array:
    """One ring rotation (``lax.ppermute``), under the ring's scope."""
    with jax.named_scope(RING):
        return lax.ppermute(x, axis_name, perm)


@scoped(AGGREGATE)
def mgg_aggregate(
    x: jax.Array,
    plan: AggregationPlan,
    mesh: Mesh,
    *,
    axis_name: str = "ring",
    interleave: bool = True,
    use_kernel: bool = False,
    acc_dtype=jnp.float32,
    pb: Optional[int] = None,
    update_w: Optional[jax.Array] = None,
    transpose: Optional[Callable[[], AggregationPlan]] = None,
) -> jax.Array:
    """Pipelined sum-aggregation: ``out[v] = Σ_{u ∈ N(v)} x[u]``.

    ``x`` is the padded PGAS embedding table ``(n_dev · rows_per_dev, D)``
    sharded by rows over ``axis_name`` (see placement.pad_embeddings); the
    output has the same layout/sharding.  ``pb`` is the paper's wpb knob:
    the partition-block height of the kernel variant (kernel path only).

    ``transpose`` returns the plan of the transposed graph
    (placement.transposed_plan, or ``plan`` itself on a symmetric graph).
    Given it, and no ``update_w``, the aggregation's backward is
    ``x̄ = Aᵀ ȳ``: this same ring over that plan, whose gather-sum reads
    ``ȳ`` where autodiff would scatter-add every gathered row.  It is
    called when a backward is traced, so a forward-only program never
    builds it.

    ``update_w`` (``(D, D_out)``, replicated) selects the **fused update**
    path: the output becomes ``(A x) @ W`` and each ring step performs its
    tile's partial ``·W`` matmul right after the gather+reduce, inside the
    same step that already issued the next tile's ppermute — so the update
    phase's MXU FLOPs overlap the next tile's ICI transfer instead of
    running as a separate post-ring matmul.  Because matmul distributes
    over the partial sums, ``Σ_s (partial_s @ W) == (Σ_s partial_s) @ W``
    exactly in reals; in floats the two paths differ only by summation
    order (tolerance-tested in tests/test_layer_plans.py).
    """
    ring = functools.partial(
        _ring_aggregate, mesh=mesh, axis_name=axis_name,
        interleave=interleave, use_kernel=use_kernel, acc_dtype=acc_dtype,
        pb=pb)
    if update_w is not None or transpose is None:
        return ring(x, plan, update_w)

    @jax.custom_vjp
    def aggregate(x):
        return ring(x, plan)

    def forward(x):
        return ring(x, plan), None     # linear in x: nothing to keep

    def backward(_, y_bar):
        return (mgg_aggregate(
            y_bar, transpose(), mesh, axis_name=axis_name,
            interleave=interleave, use_kernel=use_kernel,
            acc_dtype=acc_dtype, pb=pb, transpose=lambda: plan),)

    aggregate.defvjp(forward, backward)
    return aggregate(x)


def _ring_aggregate(x, plan, update_w=None, *, mesh, axis_name, interleave,
                    use_kernel, acc_dtype, pb):
    n_dev, dist, tile_rows = plan.n_dev, plan.dist, plan.tile_rows
    arrays = jax.tree.map(jnp.asarray, plan_device_arrays(plan))

    body = functools.partial(
        _mgg_shard_body,
        axis_name=axis_name,
        n_dev=n_dev,
        dist=dist,
        tile_rows=tile_rows,
        interleave=interleave,
        use_kernel=use_kernel,
        acc_dtype=acc_dtype,
        pb=pb,
        fused=update_w is not None,
    )
    in_specs = [P(axis_name), {k: P(axis_name) for k in arrays}]
    args = [x, arrays]
    if update_w is not None:
        in_specs.append(P(None, None))  # replicated update weight
        args.append(update_w)
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=P(axis_name),
        # Pallas calls inside the body produce vma-less ShapeDtypeStructs;
        # skip the varying-manual-axes check (correctness is oracle-tested).
        check_vma=False,
    )
    return fn(*args)


def _mgg_shard_body(
    x, arrays, w=None, *, axis_name, n_dev, dist, tile_rows, interleave,
    use_kernel, acc_dtype, pb=None, fused=False,
):
    # Per-device blocks: squeeze the device-major axis.  A maskless plan
    # has no masks: its padding slots index the row past their buffer.
    l_nbrs = arrays["local_nbrs"][0]        # (PL, ps)
    l_mask = arrays["local_mask"][0] if "local_mask" in arrays else None
    l_tgt = arrays["local_targets"][0]      # (PL,)
    r_nbrs = arrays["remote_nbrs"][0]       # (S, PR, ps)
    r_mask = arrays["remote_mask"][0] if "remote_mask" in arrays else None
    r_tgt = arrays["remote_targets"][0]     # (S, PR)

    rows, d_feat = x.shape
    step_slice = lambda a, i: None if a is None else \
        lax.dynamic_index_in_dim(a, i, 0, keepdims=False)

    def gather_sum(buf, nbrs, mask):
        if mask is None:
            return _padded_gather_sum(buf, nbrs, use_kernel, acc_dtype, pb)
        return _gather_sum(buf, nbrs, mask, use_kernel, acc_dtype, pb)

    if fused:
        wacc = w.astype(acc_dtype)
        d_out = wacc.shape[1]
        # Fused update: every partial aggregate does its ·W matmul before
        # the scatter-add, so the MXU work lands inside the ring step whose
        # next-tile ppermute is already in flight.
        update = lambda partial: partial @ wacc
    else:
        d_out = d_feat
        update = lambda partial: partial
    # Mark the accumulator as device-varying so it can be carried through the
    # ring fori_loop (shard_map vma typing).
    out = jnp.zeros((rows, d_out), acc_dtype)
    out = lax.pcast(out, (axis_name,), to="varying")
    n_steps = r_nbrs.shape[0] if n_dev > 1 else 0

    # ---- local work scheduling (paper §3.3 interleaving) -------------------
    if interleave and n_steps > 0:
        pl_total = l_nbrs.shape[0]
        ls = -(-pl_total // n_steps)  # ceil: local partitions per ring step
        pad = ls * n_steps - pl_total
        l_nbrs_s = jnp.pad(l_nbrs, ((0, pad), (0, 0)),
                           constant_values=0 if l_mask is not None else rows
                           ).reshape(n_steps, ls, -1)
        l_mask_s = None if l_mask is None else jnp.pad(
            l_mask, ((0, pad), (0, 0))).reshape(n_steps, ls, -1)
        l_tgt_s = jnp.pad(l_tgt, ((0, pad),)).reshape(n_steps, ls)
    else:
        # Paper Fig. 9(b) baseline: all local partitions up front, then the
        # (non-overlapped-with-local) remote rounds.
        out = out.at[l_tgt].add(update(gather_sum(x, l_nbrs, l_mask)))

    if n_dev == 1:
        return out.astype(x.dtype)

    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
    tiles = x.reshape(dist, tile_rows, d_feat)

    def step_work(out, cur, idx):
        """Aggregate remote tile `cur` for ring step `idx` (+ its local slice)."""
        nbrs = step_slice(r_nbrs, idx)
        mask = step_slice(r_mask, idx)
        tgt = step_slice(r_tgt, idx)
        out = out.at[tgt].add(update(gather_sum(cur, nbrs, mask)))
        if interleave:
            ln = step_slice(l_nbrs_s, idx)
            lm = step_slice(l_mask_s, idx)
            lt = step_slice(l_tgt_s, idx)
            out = out.at[lt].add(update(gather_sum(x, ln, lm)))
        return out

    # One double-buffered ring per tile chunk (chunk-major, so every chunk
    # performs exactly n_dev - 1 permutes — no wasted trailing rotation).
    for c in range(dist):
        cur = _rotate(tiles[c], axis_name, perm)  # rotation 1 (prologue)

        def body(k, carry, c=c):
            cur, out = carry
            nxt = _rotate(cur, axis_name, perm)       # rotation k+2 — no dep
            out = step_work(out, cur, k * dist + c)   # on the aggregation ⇒
            return (nxt, out)                          # XLA overlaps DMA+compute

        cur, out = lax.fori_loop(0, n_dev - 2, body, (cur, out))
        out = step_work(out, cur, (n_dev - 2) * dist + c)  # epilogue (drain)

    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MGG sparse ring aggregation: top-k compressed payload on the wire
# ---------------------------------------------------------------------------

@scoped(AGGREGATE)
def mgg_aggregate_sparse(
    x: jax.Array,
    plan: AggregationPlan,
    mesh: Mesh,
    *,
    k: int,
    axis_name: str = "ring",
    interleave: bool = True,
    use_kernel: bool = False,
    acc_dtype=jnp.float32,
    pb: Optional[int] = None,
    update_w: Optional[jax.Array] = None,
) -> jax.Array:
    """Sparse-payload variant of :func:`mgg_aggregate`.

    ``x`` is first compressed row-wise with :func:`topk_activation`; the
    ring then ppermutes the ``(values, col_idx)`` pair — ``k · (4 + 2)``
    bytes per row instead of ``D · 4`` — and every step decompresses its
    arriving tile *inside* the step before the same fixed-order masked
    gather+reduce the dense path runs.  The schedule (chunk-major rings,
    interleaved local slices, fused ``·W``) is byte-for-byte the dense
    one's, so:

    * at ``k == D`` the output is **bitwise-equal** to dense
      :func:`mgg_aggregate` (decompression is an exact inverse);
    * at ``k < D`` the output is the deterministic aggregation of the
      top-k-sparsified features — an accuracy/speed trade the caller (the
      tuner's ``k_space``) opts into explicitly.
    """
    n_dev, dist, tile_rows = plan.n_dev, plan.dist, plan.tile_rows
    d_feat = x.shape[1]
    k = int(min(k, d_feat))
    values, idx = topk_activation(x, k)
    idx = idx.astype(wire_index_dtype(d_feat))
    arrays = jax.tree.map(jnp.asarray, plan_device_arrays(plan))

    body = functools.partial(
        _mgg_sparse_shard_body,
        axis_name=axis_name,
        n_dev=n_dev,
        dist=dist,
        tile_rows=tile_rows,
        d_feat=d_feat,
        interleave=interleave,
        use_kernel=use_kernel,
        acc_dtype=acc_dtype,
        pb=pb,
        fused=update_w is not None,
    )
    in_specs = [P(axis_name), P(axis_name), _plan_specs(axis_name)]
    args = [values, idx, arrays]
    if update_w is not None:
        in_specs.append(P(None, None))  # replicated update weight
        args.append(update_w)
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=P(axis_name),
        check_vma=False,
    )
    return fn(*args)


def _mgg_sparse_shard_body(
    values, idx, arrays, w=None, *, axis_name, n_dev, dist, tile_rows,
    d_feat, interleave, use_kernel, acc_dtype, pb=None, fused=False,
):
    """Mirror of :func:`_mgg_shard_body` over the compressed payload."""
    l_nbrs = arrays["local_nbrs"][0]        # (PL, ps)
    l_mask = arrays["local_mask"][0]
    l_tgt = arrays["local_targets"][0]      # (PL,)
    r_nbrs = arrays["remote_nbrs"][0]       # (S, PR, ps)
    r_mask = arrays["remote_mask"][0]
    r_tgt = arrays["remote_targets"][0]     # (S, PR)

    rows, k = values.shape
    if fused:
        wacc = w.astype(acc_dtype)
        d_out = wacc.shape[1]
        update = lambda partial: partial @ wacc
    else:
        d_out = d_feat
        update = lambda partial: partial
    gather = lambda v, i, nb, mk: _sparse_gather_sum(
        v, i, nb, mk, d_feat, use_kernel, acc_dtype, pb)
    out = jnp.zeros((rows, d_out), acc_dtype)
    out = lax.pcast(out, (axis_name,), to="varying")
    n_steps = r_nbrs.shape[0] if n_dev > 1 else 0

    if interleave and n_steps > 0:
        pl_total = l_nbrs.shape[0]
        ls = -(-pl_total // n_steps)  # ceil: local partitions per ring step
        pad = ls * n_steps - pl_total
        l_nbrs_s = jnp.pad(l_nbrs, ((0, pad), (0, 0))).reshape(n_steps, ls, -1)
        l_mask_s = jnp.pad(l_mask, ((0, pad), (0, 0))).reshape(n_steps, ls, -1)
        l_tgt_s = jnp.pad(l_tgt, ((0, pad),)).reshape(n_steps, ls)
    else:
        out = out.at[l_tgt].add(update(gather(values, idx, l_nbrs, l_mask)))

    if n_dev == 1:
        return out.astype(values.dtype)

    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
    v_tiles = values.reshape(dist, tile_rows, k)
    i_tiles = idx.reshape(dist, tile_rows, k)

    def step_work(out, cur_v, cur_i, step):
        nbrs = lax.dynamic_index_in_dim(r_nbrs, step, 0, keepdims=False)
        mask = lax.dynamic_index_in_dim(r_mask, step, 0, keepdims=False)
        tgt = lax.dynamic_index_in_dim(r_tgt, step, 0, keepdims=False)
        out = out.at[tgt].add(update(gather(cur_v, cur_i, nbrs, mask)))
        if interleave:
            ln = lax.dynamic_index_in_dim(l_nbrs_s, step, 0, keepdims=False)
            lm = lax.dynamic_index_in_dim(l_mask_s, step, 0, keepdims=False)
            lt = lax.dynamic_index_in_dim(l_tgt_s, step, 0, keepdims=False)
            out = out.at[lt].add(update(gather(values, idx, ln, lm)))
        return out

    # Same chunk-major double-buffered rings as the dense body — only the
    # payload narrows: both halves of the compressed pair ride each rotation.
    for c in range(dist):
        cur_v = _rotate(v_tiles[c], axis_name, perm)
        cur_i = _rotate(i_tiles[c], axis_name, perm)

        def body(s, carry, c=c):
            cur_v, cur_i, out = carry
            nxt_v = _rotate(cur_v, axis_name, perm)  # rotation s+2
            nxt_i = _rotate(cur_i, axis_name, perm)  # — no dep on the
            out = step_work(out, cur_v, cur_i, s * dist + c)  # aggregation
            return (nxt_v, nxt_i, out)

        cur_v, cur_i, out = lax.fori_loop(
            0, n_dev - 2, body, (cur_v, cur_i, out))
        out = step_work(out, cur_v, cur_i, (n_dev - 2) * dist + c)

    return out.astype(values.dtype)


# ---------------------------------------------------------------------------
# MGG streamed aggregation: tiered features, host→device prefetch in the ring
# ---------------------------------------------------------------------------

def mgg_aggregate_streamed(
    fetch_chunk,
    plan: AggregationPlan,
    mesh: Mesh,
    *,
    axis_name: str = "ring",
    use_kernel: bool = False,
    acc_dtype=jnp.float32,
    pb: Optional[int] = None,
    update_w: Optional[jax.Array] = None,
    stats: Optional[dict] = None,
    tracer=None,
) -> jax.Array:
    """Pipelined aggregation over *partial-resident* features.

    ``fetch_chunk(c)`` supplies ring chunk ``c`` on demand — the
    ``(n_dev · tile_rows, D)`` array holding every device's chunk-``c``
    tile (see :meth:`repro.store.TieredFeatures.device_chunk`, which
    sources rows from the device hot cache or a host-side gather).  The
    schedule is the double-buffered prefetch of the tentpole:

    1. fetch chunk 0 (pipeline fill — the one gather nothing can hide);
    2. for each chunk ``c``: dispatch chunk ``c``'s remote ring
       asynchronously, then immediately call ``fetch_chunk(c + 1)`` —
       the host row gather and ``device_put`` upload for tile *i+1* run
       while tile *i*'s ppermute ring is in flight (same independence
       the in-ring double buffer gives XLA, lifted to the host side);
    3. once every chunk is resident, run the local pass over the
       assembled shard and sum: ``out = local + Σ_c ring_c``.

    The sum order is fixed (local first, then chunks in order), so the
    result is **deterministic and independent of row sourcing**: any
    capacity — including all-resident — produces bitwise-identical
    output through this path.  Against :func:`mgg_aggregate` the result
    differs only by scatter-add accumulation order (tolerance-tested);
    there is no ``interleave`` knob here because the local pass cannot
    start before the last chunk lands.

    ``stats`` (optional dict) is updated in place with prefetch
    accounting: ``prefetch_issued`` counts fetches issued while the
    previous chunk's ring was already dispatched (structural overlap,
    ``dist - 1`` per call), ``prefetch_inflight`` counts those where the
    ring result was verifiably still unrealized when the fetch returned.

    ``tracer`` (optional :class:`repro.obs.Tracer`) records the ring-step
    timeline: per-chunk ``mgg.stream.fetch`` / ``mgg.stream.ring`` spans,
    the assembled local pass, and an explicit drain wait, rolled up into
    an ``mgg.stream.aggregate`` span whose ``overlap_efficiency`` arg is
    ``1 − exposed_comm / total`` (exposed = pipeline-fill fetch + drain —
    the transfer time nothing overlaps).  Only span bookkeeping differs
    with tracing on: the output value is identical, and the enabled path
    adds one extra ``block_until_ready`` that the caller's own drain would
    otherwise pay.
    """
    n_dev, dist, tile_rows = plan.n_dev, plan.dist, plan.tile_rows
    arrays = jax.tree.map(jnp.asarray, plan_device_arrays(plan))
    if stats is not None:
        stats.setdefault("prefetch_issued", 0)
        stats.setdefault("prefetch_inflight", 0)
    tracing = tracer is not None and tracer.enabled

    fused = update_w is not None
    extra = (update_w,) if fused else ()
    chunks = []
    partials = []
    if tracing:
        t_start = tracer.now()
        t0 = tracer.now()
        cur = fetch_chunk(0)
        t_fill = tracer.now() - t0             # pipeline fill (not hidden)
        tracer.complete("mgg.stream.fetch", t0, t0 + t_fill,
                        cat="mgg", args={"chunk": 0, "fill": True})
    else:
        cur = fetch_chunk(0)                   # pipeline fill (not hidden)
    for c in range(dist):
        chunks.append(cur)
        if n_dev > 1:
            # dispatched asynchronously: returns before the ring executes
            ring = _streamed_ring_fn(mesh, axis_name, n_dev, dist, c,
                                     use_kernel, acc_dtype, pb, fused)
            if tracing:
                with tracer.span("mgg.stream.ring", cat="mgg", chunk=c,
                                 dist=dist, n_dev=n_dev):
                    partials.append(ring(cur, arrays, *extra))
            else:
                partials.append(ring(cur, arrays, *extra))
        if c + 1 < dist:
            # host gather + upload for tile c+1 overlaps ring c in flight
            if tracing:
                with tracer.span("mgg.stream.fetch", cat="mgg",
                                 chunk=c + 1, fill=False):
                    cur = fetch_chunk(c + 1)
            else:
                cur = fetch_chunk(c + 1)
            if stats is not None:
                stats["prefetch_issued"] += 1
                last = partials[-1] if partials else None
                if last is not None and hasattr(last, "is_ready") \
                        and not last.is_ready():
                    stats["prefetch_inflight"] += 1

    x_full = _streamed_assemble_fn(mesh, axis_name, n_dev, dist)(*chunks)
    local = _streamed_local_fn(mesh, axis_name, use_kernel, acc_dtype, pb,
                               fused)
    if tracing:
        with tracer.span("mgg.stream.local", cat="mgg", dist=dist):
            out = local(x_full, arrays, *extra)
    else:
        out = local(x_full, arrays, *extra)
    for p in partials:                         # fixed order ⇒ deterministic
        out = out + p
    out = out.astype(chunks[0].dtype)
    if tracing:
        # drain: the wait nothing overlaps.  block_until_ready changes
        # only when the host observes completion, never the values.
        t0 = tracer.now()
        jax.block_until_ready(out)
        t_drain = tracer.now() - t0
        tracer.complete("mgg.stream.drain", t0, t0 + t_drain, cat="mgg")
        total = tracer.now() - t_start
        exposed = t_fill + t_drain
        overlap = max(0.0, 1.0 - exposed / total) if total > 0 else 0.0
        tracer.complete("mgg.stream.aggregate", t_start, t_start + total,
                        cat="mgg",
                        args={"dist": dist, "n_dev": n_dev,
                              "overlap_efficiency": overlap,
                              "exposed_s": exposed, "total_s": total})
        if stats is not None:
            stats["overlap_efficiency"] = overlap
    return out


# The streamed entry point is called once per chunk per aggregation, so —
# unlike mgg_aggregate, whose single shard_map is traced per call — its
# compiled pieces are memoized on their static configuration (the arrays
# pytree and tiles are traced arguments, so one cache entry serves every
# plan with the same shapes).

@functools.lru_cache(maxsize=None)
def _streamed_ring_fn(mesh, axis_name, n_dev, dist, chunk, use_kernel,
                      acc_dtype, pb, fused):
    body = functools.partial(
        _streamed_chunk_body, axis_name=axis_name, n_dev=n_dev, dist=dist,
        chunk=chunk, use_kernel=use_kernel, acc_dtype=acc_dtype, pb=pb,
        fused=fused,
    )
    in_specs = [P(axis_name), _plan_specs(axis_name)]
    if fused:
        in_specs.append(P(None, None))
    # jit the shard_map: a bare shard_map re-traces on every call, and
    # the streamed path issues dist of these per aggregation
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                                 out_specs=P(axis_name), check_vma=False))


@functools.lru_cache(maxsize=None)
def _streamed_local_fn(mesh, axis_name, use_kernel, acc_dtype, pb, fused):
    body = functools.partial(
        _streamed_local_body, axis_name=axis_name, use_kernel=use_kernel,
        acc_dtype=acc_dtype, pb=pb, fused=fused,
    )
    in_specs = [P(axis_name), _plan_specs(axis_name)]
    if fused:
        in_specs.append(P(None, None))
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                                 out_specs=P(axis_name), check_vma=False))


@functools.lru_cache(maxsize=None)
def _streamed_assemble_fn(mesh, axis_name, n_dev, dist):
    """Chunk arrays → the full shard (chunk-minor → row-major per device)."""
    @scoped(AGGREGATE)
    def assemble(*chs):
        tile_rows = chs[0].shape[0] // n_dev
        st = jnp.stack(chs, axis=0)            # (dist, n_dev·tile, D)
        st = st.reshape(dist, n_dev, tile_rows, -1).transpose(1, 0, 2, 3)
        return st.reshape(n_dev * dist * tile_rows, -1)

    return jax.jit(assemble,
                   out_shardings=NamedSharding(mesh, P(axis_name)))


def _streamed_step(out, cur, idx, r_nbrs, r_mask, r_tgt, update,
                   use_kernel, acc_dtype, pb):
    nbrs = lax.dynamic_index_in_dim(r_nbrs, idx, 0, keepdims=False)
    mask = lax.dynamic_index_in_dim(r_mask, idx, 0, keepdims=False)
    tgt = lax.dynamic_index_in_dim(r_tgt, idx, 0, keepdims=False)
    return out.at[tgt].add(
        update(_gather_sum(cur, nbrs, mask, use_kernel, acc_dtype, pb)))


def _streamed_init(w, d_feat, acc_dtype, fused):
    """(update fn, output width) — fused folds the ·W matmul into every
    partial aggregate, exactly as in :func:`mgg_aggregate`."""
    if fused:
        wacc = w.astype(acc_dtype)
        return (lambda partial: partial @ wacc), int(wacc.shape[1])
    return (lambda partial: partial), d_feat


@scoped(AGGREGATE)
def _streamed_chunk_body(tile, arrays, w=None, *, axis_name, n_dev, dist,
                         chunk, use_kernel, acc_dtype, pb=None, fused=False):
    """One chunk's remote ring: only the steps ``s`` with
    ``s % dist == chunk`` — i.e. the rotations of this chunk's tile."""
    r_nbrs = arrays["remote_nbrs"][0]       # (S, PR, ps)
    r_mask = arrays["remote_mask"][0]
    r_tgt = arrays["remote_targets"][0]
    rows = dist * tile.shape[0]             # shard height = dist · tile_rows
    update, d_out = _streamed_init(w, tile.shape[1], acc_dtype, fused)
    out = jnp.zeros((rows, d_out), acc_dtype)
    out = lax.pcast(out, (axis_name,), to="varying")

    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
    cur = _rotate(tile, axis_name, perm)  # rotation 1 (prologue)

    def body(k, carry):
        cur, out = carry
        nxt = _rotate(cur, axis_name, perm)   # rotation k+2 — no dep
        out = _streamed_step(out, cur, k * dist + chunk, r_nbrs, r_mask,
                             r_tgt, update, use_kernel, acc_dtype, pb)
        return (nxt, out)

    cur, out = lax.fori_loop(0, n_dev - 2, body, (cur, out))
    out = _streamed_step(out, cur, (n_dev - 2) * dist + chunk, r_nbrs,
                         r_mask, r_tgt, update, use_kernel, acc_dtype, pb)
    return out


@scoped(AGGREGATE)
def _streamed_local_body(x, arrays, w=None, *, axis_name, use_kernel,
                         acc_dtype, pb=None, fused=False):
    """The local pass over the fully assembled shard (runs last)."""
    l_nbrs = arrays["local_nbrs"][0]
    l_mask = arrays["local_mask"][0]
    l_tgt = arrays["local_targets"][0]
    update, d_out = _streamed_init(w, x.shape[1], acc_dtype, fused)
    out = jnp.zeros((x.shape[0], d_out), acc_dtype)
    out = lax.pcast(out, (axis_name,), to="varying")
    return out.at[l_tgt].add(
        update(_gather_sum(x, l_nbrs, l_mask, use_kernel, acc_dtype, pb)))


# ---------------------------------------------------------------------------
# MGG sparse streamed aggregation: compressed wire + tiered host features
# ---------------------------------------------------------------------------

def mgg_aggregate_sparse_streamed(
    fetch_chunk,
    plan: AggregationPlan,
    mesh: Mesh,
    *,
    k: int,
    axis_name: str = "ring",
    use_kernel: bool = False,
    acc_dtype=jnp.float32,
    pb: Optional[int] = None,
    update_w: Optional[jax.Array] = None,
    stats: Optional[dict] = None,
    tracer=None,
) -> jax.Array:
    """Sparse-payload variant of :func:`mgg_aggregate_streamed`.

    ``fetch_chunk`` keeps the dense contract (it sources rows from the
    tiered store); each chunk is compressed on device with
    :func:`topk_activation` right after it lands, so the ring rotations —
    the part the host prefetch overlaps — carry the narrow
    ``(values, col_idx)`` pair.  The local pass runs over the assembled
    compressed shard.  Sum order matches the dense streamed path exactly,
    so at ``k == D`` the output is bitwise-equal to
    :func:`mgg_aggregate_streamed` at any cache capacity.
    """
    n_dev, dist, tile_rows = plan.n_dev, plan.dist, plan.tile_rows
    arrays = jax.tree.map(jnp.asarray, plan_device_arrays(plan))
    if stats is not None:
        stats.setdefault("prefetch_issued", 0)
        stats.setdefault("prefetch_inflight", 0)
    tracing = tracer is not None and tracer.enabled

    fused = update_w is not None
    extra = (update_w,) if fused else ()
    v_chunks = []
    i_chunks = []
    partials = []
    compress = None
    d_feat = None

    def _land(chunk):
        """Compress a freshly fetched dense chunk on device."""
        nonlocal compress, d_feat
        if compress is None:
            d_feat = int(chunk.shape[1])
            wire = jnp.dtype(wire_index_dtype(d_feat)).name
            compress = _sparse_compress_fn(mesh, axis_name,
                                           int(min(k, d_feat)), wire)
        return compress(chunk)

    if tracing:
        t_start = tracer.now()
        t0 = tracer.now()
        cur = _land(fetch_chunk(0))
        t_fill = tracer.now() - t0             # pipeline fill (not hidden)
        tracer.complete("mgg.stream.fetch", t0, t0 + t_fill,
                        cat="mgg", args={"chunk": 0, "fill": True})
    else:
        cur = _land(fetch_chunk(0))            # pipeline fill (not hidden)
    for c in range(dist):
        v_chunks.append(cur[0])
        i_chunks.append(cur[1])
        if n_dev > 1:
            ring = _sparse_streamed_ring_fn(
                mesh, axis_name, n_dev, dist, c, d_feat,
                use_kernel, acc_dtype, pb, fused)
            if tracing:
                with tracer.span("mgg.stream.ring", cat="mgg", chunk=c,
                                 dist=dist, n_dev=n_dev, sparse_k=k):
                    partials.append(ring(cur[0], cur[1], arrays, *extra))
            else:
                partials.append(ring(cur[0], cur[1], arrays, *extra))
        if c + 1 < dist:
            if tracing:
                with tracer.span("mgg.stream.fetch", cat="mgg",
                                 chunk=c + 1, fill=False):
                    cur = _land(fetch_chunk(c + 1))
            else:
                cur = _land(fetch_chunk(c + 1))
            if stats is not None:
                stats["prefetch_issued"] += 1
                last = partials[-1] if partials else None
                if last is not None and hasattr(last, "is_ready") \
                        and not last.is_ready():
                    stats["prefetch_inflight"] += 1

    assemble = _streamed_assemble_fn(mesh, axis_name, n_dev, dist)
    v_full = assemble(*v_chunks)
    i_full = assemble(*i_chunks)
    local = _sparse_streamed_local_fn(mesh, axis_name, d_feat, use_kernel,
                                      acc_dtype, pb, fused)
    if tracing:
        with tracer.span("mgg.stream.local", cat="mgg", dist=dist):
            out = local(v_full, i_full, arrays, *extra)
    else:
        out = local(v_full, i_full, arrays, *extra)
    for p in partials:                         # fixed order ⇒ deterministic
        out = out + p
    out = out.astype(v_chunks[0].dtype)
    if tracing:
        t0 = tracer.now()
        jax.block_until_ready(out)
        t_drain = tracer.now() - t0
        tracer.complete("mgg.stream.drain", t0, t0 + t_drain, cat="mgg")
        total = tracer.now() - t_start
        exposed = t_fill + t_drain
        overlap = max(0.0, 1.0 - exposed / total) if total > 0 else 0.0
        tracer.complete("mgg.stream.aggregate", t_start, t_start + total,
                        cat="mgg",
                        args={"dist": dist, "n_dev": n_dev, "sparse_k": k,
                              "overlap_efficiency": overlap,
                              "exposed_s": exposed, "total_s": total})
        if stats is not None:
            stats["overlap_efficiency"] = overlap
    return out


@functools.lru_cache(maxsize=None)
def _sparse_compress_fn(mesh, axis_name, k, wire_dtype_name):
    """jitted per-chunk row-wise top-k compression, sharding-preserving."""
    @scoped(AGGREGATE)
    def compress(chunk):
        values, idx = lax.top_k(chunk, k)
        return values, idx.astype(jnp.dtype(wire_dtype_name))

    sharding = NamedSharding(mesh, P(axis_name))
    return jax.jit(compress, out_shardings=(sharding, sharding))


@functools.lru_cache(maxsize=None)
def _sparse_streamed_ring_fn(mesh, axis_name, n_dev, dist, chunk, d_feat,
                             use_kernel, acc_dtype, pb, fused):
    body = functools.partial(
        _sparse_streamed_chunk_body, axis_name=axis_name, n_dev=n_dev,
        dist=dist, chunk=chunk, d_feat=d_feat, use_kernel=use_kernel,
        acc_dtype=acc_dtype, pb=pb, fused=fused,
    )
    in_specs = [P(axis_name), P(axis_name), _plan_specs(axis_name)]
    if fused:
        in_specs.append(P(None, None))
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                                 out_specs=P(axis_name), check_vma=False))


@functools.lru_cache(maxsize=None)
def _sparse_streamed_local_fn(mesh, axis_name, d_feat, use_kernel, acc_dtype,
                              pb, fused):
    body = functools.partial(
        _sparse_streamed_local_body, axis_name=axis_name, d_feat=d_feat,
        use_kernel=use_kernel, acc_dtype=acc_dtype, pb=pb, fused=fused,
    )
    in_specs = [P(axis_name), P(axis_name), _plan_specs(axis_name)]
    if fused:
        in_specs.append(P(None, None))
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                                 out_specs=P(axis_name), check_vma=False))


@scoped(AGGREGATE)
def _sparse_streamed_chunk_body(v_tile, i_tile, arrays, w=None, *, axis_name,
                                n_dev, dist, chunk, d_feat, use_kernel,
                                acc_dtype, pb=None, fused=False):
    """One chunk's remote ring over the compressed payload."""
    r_nbrs = arrays["remote_nbrs"][0]       # (S, PR, ps)
    r_mask = arrays["remote_mask"][0]
    r_tgt = arrays["remote_targets"][0]
    rows = dist * v_tile.shape[0]           # shard height = dist · tile_rows
    update, d_out = _streamed_init(w, d_feat, acc_dtype, fused)
    out = jnp.zeros((rows, d_out), acc_dtype)
    out = lax.pcast(out, (axis_name,), to="varying")

    def step(out, cur_v, cur_i, idx):
        nbrs = lax.dynamic_index_in_dim(r_nbrs, idx, 0, keepdims=False)
        mask = lax.dynamic_index_in_dim(r_mask, idx, 0, keepdims=False)
        tgt = lax.dynamic_index_in_dim(r_tgt, idx, 0, keepdims=False)
        return out.at[tgt].add(update(_sparse_gather_sum(
            cur_v, cur_i, nbrs, mask, d_feat, use_kernel, acc_dtype, pb)))

    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
    cur_v = _rotate(v_tile, axis_name, perm)  # rotation 1 (prologue)
    cur_i = _rotate(i_tile, axis_name, perm)

    def body(s, carry):
        cur_v, cur_i, out = carry
        nxt_v = _rotate(cur_v, axis_name, perm)   # rotation s+2 — no dep
        nxt_i = _rotate(cur_i, axis_name, perm)
        out = step(out, cur_v, cur_i, s * dist + chunk)
        return (nxt_v, nxt_i, out)

    cur_v, cur_i, out = lax.fori_loop(0, n_dev - 2, body,
                                      (cur_v, cur_i, out))
    out = step(out, cur_v, cur_i, (n_dev - 2) * dist + chunk)
    return out


@scoped(AGGREGATE)
def _sparse_streamed_local_body(values, idx, arrays, w=None, *, axis_name,
                                d_feat, use_kernel, acc_dtype, pb=None,
                                fused=False):
    """The local pass over the assembled compressed shard (runs last)."""
    l_nbrs = arrays["local_nbrs"][0]
    l_mask = arrays["local_mask"][0]
    l_tgt = arrays["local_targets"][0]
    update, d_out = _streamed_init(w, d_feat, acc_dtype, fused)
    out = jnp.zeros((values.shape[0], d_out), acc_dtype)
    out = lax.pcast(out, (axis_name,), to="varying")
    return out.at[l_tgt].add(update(_sparse_gather_sum(
        values, idx, l_nbrs, l_mask, d_feat, use_kernel, acc_dtype, pb)))


# ---------------------------------------------------------------------------
# Baseline 1: bulk all-gather + local aggregation (DGCL / NCCL pattern)
# ---------------------------------------------------------------------------

@scoped(AGGREGATE)
def bulk_aggregate(
    x: jax.Array,
    bulk_nbrs: np.ndarray,   # (n_dev, P, ps) offsets into the padded table
    bulk_mask: np.ndarray,
    bulk_targets: np.ndarray,  # (n_dev, P)
    rows_per_dev: int,
    mesh: Mesh,
    *,
    axis_name: str = "ring",
    use_kernel: bool = False,
    acc_dtype=jnp.float32,
) -> jax.Array:
    """All-gather the entire table first, aggregate second (no overlap)."""

    def body(x, nbrs, mask, tgt):
        full = lax.all_gather(x, axis_name, axis=0, tiled=True)
        out = jnp.zeros((x.shape[0], x.shape[1]), acc_dtype)
        out = out.at[tgt[0]].add(
            _gather_sum(full, nbrs[0], mask[0], use_kernel, acc_dtype)
        )
        return out.astype(x.dtype)

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(axis_name), P(axis_name)),
        out_specs=P(axis_name),
        check_vma=False,
    )
    return fn(x, jnp.asarray(bulk_nbrs), jnp.asarray(bulk_mask),
              jnp.asarray(bulk_targets))


# ---------------------------------------------------------------------------
# Baseline 2: fetch-then-aggregate with a granularity knob (UVM / Direct)
# ---------------------------------------------------------------------------

@scoped(AGGREGATE)
def fetch_rows_aggregate(
    x: jax.Array,
    fetch_rows: np.ndarray,   # (n_dev, F) padded-global row ids to fetch
    nbrs: np.ndarray,         # (n_dev, P, ps) offsets into the fetched buffer
    mask: np.ndarray,
    targets: np.ndarray,      # (n_dev, P)
    out_rows: int,
    *,
    use_kernel: bool = False,
    acc_dtype=jnp.float32,
) -> jax.Array:
    """Gather ``fetch_rows`` from the global table, then aggregate locally.

    Cost-model baseline (single-program execution): with exact rows this is
    the Direct-NVSHMEM pattern of Table 1; with page-expanded rows it is the
    UVM pattern of §2.2 — the gather volume, not the aggregation math,
    changes.  No communication/computation overlap by construction.
    """

    def per_dev(rows_ids, nb, mk, tg):
        buf = jnp.take(x, rows_ids, axis=0)
        partial = _gather_sum(buf, nb, mk, use_kernel, acc_dtype)
        out = jnp.zeros((out_rows, x.shape[1]), acc_dtype)
        return out.at[tg].add(partial).astype(x.dtype)

    return jax.vmap(per_dev)(
        jnp.asarray(fetch_rows), jnp.asarray(nbrs), jnp.asarray(mask),
        jnp.asarray(targets),
    )


# ---------------------------------------------------------------------------
# Oracle + analytical terms
# ---------------------------------------------------------------------------

def reference_aggregate(indptr: np.ndarray, indices: np.ndarray,
                        x: np.ndarray) -> np.ndarray:
    """Dense oracle: ``out[v] = Σ_{u ∈ N(v)} x[u]`` (float64 accumulation)."""
    out = np.zeros_like(x, dtype=np.float64)
    deg = np.diff(indptr)
    row_ids = np.repeat(np.arange(x.shape[0]), deg)
    np.add.at(out, row_ids, x[indices].astype(np.float64))
    return out.astype(x.dtype)


def collective_bytes(plan: AggregationPlan, d_feat: int, itemsize: int = 4) -> int:
    """ICI bytes per device per aggregation: (n-1) full shard rotations."""
    if plan.n_dev <= 1:
        return 0
    return (plan.n_dev - 1) * plan.rows_per_dev * d_feat * itemsize


def sparse_collective_bytes(plan: AggregationPlan, d_feat: int, k: int,
                            itemsize: int = 4) -> int:
    """Ring bytes of the compressed payload: (n-1) rotations of the
    ``(values, col_idx)`` pair — ``k`` values plus ``k`` column ids in the
    wire index dtype (int16 when ``D`` fits) per row."""
    if plan.n_dev <= 1:
        return 0
    idx_itemsize = jnp.dtype(wire_index_dtype(d_feat)).itemsize
    k = min(int(k), int(d_feat))
    return (plan.n_dev - 1) * plan.rows_per_dev * k * (itemsize + idx_itemsize)
