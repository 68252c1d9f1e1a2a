"""GCN (Kipf & Welling 2017): Z = Â relu(Â X W¹ + b¹) W² + b²,
Â = D^-1/2 (A + I) D^-1/2, trained on the masked mean cross-entropy.

Reference: plain jax.numpy over the edge list (``segment_sum``), float32,
matmuls at the backend's default precision, as the configuration states.
Nothing of the program is imported.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import work

SELF_LOOPS = True          # the reference's edge list carries A + I


def dims(cfg):
    return ([cfg["features"]] + [cfg["hidden"]] * (cfg["layers"] - 1)
            + [cfg["classes"]])


def init_params(key, cfg):
    """Glorot-normal weights, zero biases, in the program's pytree."""
    d = dims(cfg)
    keys = jax.random.split(key, cfg["layers"])
    return dict(layers=[
        dict(w=jax.random.normal(k, (d[i], d[i + 1]), jnp.float32)
             * jnp.sqrt(2.0 / (d[i] + d[i + 1])),
             b=jnp.zeros((d[i + 1],), jnp.float32))
        for i, k in enumerate(keys)])


def reference_logits(params, x, src, dst, n, dtype=jnp.float32):
    """``src``/``dst`` list the edges of A + I."""
    mm = lambda a, b: jnp.matmul(a, b.astype(dtype)).astype(dtype)
    deg = jax.ops.segment_sum(jnp.ones(dst.shape, dtype), dst,
                              num_segments=n)
    dinv = jax.lax.rsqrt(jnp.maximum(deg, 1.0))[:, None]

    def agg(h):
        return dinv * jax.ops.segment_sum((h * dinv)[src], dst,
                                          num_segments=n)

    h = x.astype(dtype)
    layers = params["layers"]
    for i, p in enumerate(layers):
        d_in, d_out = p["w"].shape
        # transform first where it narrows the rows (same math either way)
        h = (agg(mm(h, p["w"])) if d_in > d_out else mm(agg(h), p["w"])) \
            + p["b"].astype(dtype)
        if i < len(layers) - 1:
            h = jax.nn.relu(h)
    return h


def work_per_step(cfg, edges_with_self_loops):
    """FLOPs (matmuls and aggregation adds, forward and backward) and the
    aggregations' required bytes of one full-graph training step."""
    n, e = cfg["nodes"], edges_with_self_loops
    d = dims(cfg)
    flops = bytes_ = 0
    for i in range(cfg["layers"]):
        d_in, d_out = d[i], d[i + 1]
        width = min(d_in, d_out)             # the aggregated rows
        mm = work.matmul_flops(n, d_in, d_out)
        # forward, weight gradient, and input gradient except for X
        flops += mm * (2 if i == 0 else 3)
        flops += 2 * work.agg_flops(e, width)  # forward and its transpose
        bytes_ += 2 * work.agg_bytes(n, e, width)
    return dict(flops=flops, agg_bytes=bytes_)
