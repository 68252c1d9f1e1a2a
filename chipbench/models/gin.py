"""GIN (Xu et al. 2019): h' = MLP((1 + ε) h + Σ_{u ∈ N(v)} h_u), MLP =
relu(relu(z W₁ + b₁) W₂ + b₂), a dense head, trained on the masked mean
cross-entropy.

Reference: plain jax.numpy over the edge list (``segment_sum``), float32,
matmuls at the backend's default precision, as the configuration states.
Nothing of the program is imported.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import work

SELF_LOOPS = False         # the reference adds (1 + ε) h itself


def _dense(key, fan_in, fan_out):
    return dict(w=jax.random.normal(key, (fan_in, fan_out), jnp.float32)
                * jnp.sqrt(2.0 / (fan_in + fan_out)),
                b=jnp.zeros((fan_out,), jnp.float32))


def init_params(key, cfg):
    """Glorot-normal weights, zero biases and ε, in the program's pytree."""
    hid, nl = cfg["hidden"], cfg["layers"]
    keys = jax.random.split(key, 2 * nl + 1)
    d = [cfg["features"]] + [hid] * nl
    return dict(
        layers=[dict(eps=jnp.zeros((), jnp.float32),
                     mlp1=_dense(keys[2 * i], d[i], hid),
                     mlp2=_dense(keys[2 * i + 1], hid, hid))
                for i in range(nl)],
        head=_dense(keys[-1], hid, cfg["classes"]))


def reference_logits(params, x, src, dst, n, dtype=jnp.float32):
    """``src``/``dst`` list the edges of A (no added self-loops)."""
    def dense(p, h):
        return jnp.matmul(h, p["w"].astype(dtype)).astype(dtype) \
            + p["b"].astype(dtype)

    h = x.astype(dtype)
    for p in params["layers"]:
        agg = jax.ops.segment_sum(h[src], dst, num_segments=n)
        z = agg + (1 + p["eps"].astype(dtype)) * h
        h = jax.nn.relu(dense(p["mlp2"], jax.nn.relu(dense(p["mlp1"], z))))
    return dense(params["head"], h)


def work_per_step(cfg, edges_with_self_loops):
    """FLOPs (matmuls and aggregation adds, forward and backward) and the
    aggregations' required bytes of one full-graph training step.  Every
    matmul needs its input gradient too (layer 0's feeds ε's gradient);
    layer 0's aggregation has no transpose (X takes no gradient)."""
    n, e = cfg["nodes"], edges_with_self_loops
    hid, ncls = cfg["hidden"], cfg["classes"]
    flops = 3 * work.matmul_flops(n, hid, ncls)
    bytes_ = 0
    for i in range(cfg["layers"]):
        d_in = cfg["features"] if i == 0 else hid
        flops += 3 * (work.matmul_flops(n, d_in, hid)
                      + work.matmul_flops(n, hid, hid))
        passes = 1 if i == 0 else 2
        flops += passes * work.agg_flops(e, d_in)
        bytes_ += passes * work.agg_bytes(n, e, d_in)
    return dict(flops=flops, agg_bytes=bytes_)
