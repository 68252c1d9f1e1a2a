"""Plain training reference and the comparison that decides ``correct``.

AdamW (Loshchilov & Hutter 2019) with global-norm clipping and a linear
warm-up into a cosine decay, written out from the published update; the
loss is the masked mean cross-entropy.  Nothing of the program is imported.

The comparison (``compare``) reads the numbers that a cell's limits name:

* ``loss``: the largest relative gap of the first steps' losses;
* ``grad``: the first gradient as the optimizer gets it (after clipping),
  worst leaf: |‖g_prog‖ − ‖g_ref‖| over max(‖g_ref‖, median leaf norm);
* ``delta``: the parameters' change over the first steps, worst leaf, by
  the same measure.

Leaves whose reference gradient norm is under a thousandth of the median
leaf's move by round-off alone under Adam and are left out of both.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

CHECK_STEPS = 3
NEGLIGIBLE = 1e-3


def masked_cross_entropy(logits, labels, mask):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def _lr(opt, count):
    step = count.astype(jnp.float32)
    warm = jnp.minimum(1.0, step / max(1, opt["warmup_steps"]))
    frac = jnp.clip((step - opt["warmup_steps"])
                    / max(1, opt["total_steps"] - opt["warmup_steps"]),
                    0.0, 1.0)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * frac))
    return opt["lr"] * warm * (opt["min_lr_ratio"]
                               + (1 - opt["min_lr_ratio"]) * cos)


def adamw_step(params, state, grads, opt):
    """One AdamW update; returns (params, state, clipped gradient)."""
    count = state["count"] + 1
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-12))
    grads = jax.tree.map(lambda g: g * scale, grads)
    lr = _lr(opt, count)
    c1 = 1 - opt["b1"] ** count.astype(jnp.float32)
    c2 = 1 - opt["b2"] ** count.astype(jnp.float32)
    m = jax.tree.map(lambda m, g: opt["b1"] * m + (1 - opt["b1"]) * g,
                     state["m"], grads)
    v = jax.tree.map(lambda v, g: opt["b2"] * v + (1 - opt["b2"]) * g * g,
                     state["v"], grads)

    def upd(p, m, v):
        u = (m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
        if p.ndim >= 2:
            u = u + opt["weight_decay"] * p
        return p - lr * u

    return (jax.tree.map(upd, params, m, v),
            dict(m=m, v=v, count=count), grads)


# The control: the reference in the nearest precision below float32 at the
# backend's default matmul precision, which the configurations state.
CONTROL_DTYPE = "bfloat16"


def train_readings(logits_fn, params, data, opt, dtype="float32",
                   steps=CHECK_STEPS):
    """Run ``steps`` reference steps from ``params``.  ``logits_fn(params,
    x, src, dst, n, dtype)``; ``data`` holds x, y, mask, src, dst, n;
    ``dtype`` names the precision of storage and arithmetic (matmuls at the
    backend's default precision).  Returns the readings ``compare``
    takes."""
    n = data["n"]
    dtype = getattr(jnp, dtype)

    def loss_fn(p, x, y, mask, src, dst):
        return masked_cross_entropy(
            logits_fn(p, x, src, dst, n, dtype), y, mask)

    @jax.jit
    def step(p, state, x, y, mask, src, dst):
        loss, grads = jax.value_and_grad(loss_fn)(p, x, y, mask, src, dst)
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        p, state, g = adamw_step(p, state, grads, opt)
        return p, state, loss.astype(jnp.float32), g

    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    state = dict(m=jax.tree.map(zeros, params),
                 v=jax.tree.map(zeros, params),
                 count=jnp.zeros((), jnp.int32))
    args = [data[k] for k in ("x", "y", "mask", "src", "dst")]
    p, losses, first_grad = params, [], None
    for i in range(steps):
        p, state, loss, g = step(p, state, *args)
        losses.append(float(loss))
        if i == 0:
            first_grad = g
    return readings(losses, first_grad, params, p)


def readings(losses, first_grad, params0, params_n):
    """Host copies: losses, first clipped gradient and parameter change,
    leaves keyed by their path."""
    leaves = lambda t: {jax.tree_util.keystr(k): np.asarray(v, np.float64)
                        for k, v in jax.tree_util.tree_leaves_with_path(t)}
    p0, pn = leaves(params0), leaves(params_n)
    return dict(losses=[float(l) for l in losses],
                grad=leaves(first_grad),
                delta={k: pn[k] - p0[k] for k in p0})


def _leaf_gaps(got, want, live):
    """Per leaf: |‖got‖ − ‖want‖| over max(‖want‖, median leaf norm)."""
    norms = {k: float(np.linalg.norm(want[k])) for k in live}
    floor = float(np.median(list(norms.values())))
    gaps = {k: abs(float(np.linalg.norm(got[k])) - norms[k])
            / max(norms[k], floor, 1e-30) for k in live}
    return {k: g if np.isfinite(g) else float("inf")
            for k, g in gaps.items()}


def compare(prog, ref):
    """The numbers a cell may compare (see the module's docstring), and in
    ``detail`` what they came from."""
    gnorm = {k: float(np.linalg.norm(v)) for k, v in ref["grad"].items()}
    med = float(np.median(list(gnorm.values())))
    live = [k for k, v in gnorm.items() if v >= NEGLIGIBLE * med]
    steps = [abs(a - b) / abs(b) if np.isfinite(a) else float("inf")
             for a, b in zip(prog["losses"], ref["losses"])]
    grad = _leaf_gaps(prog["grad"], ref["grad"], live)
    delta = _leaf_gaps(prog["delta"], ref["delta"], live)
    worst = lambda d: max(d, key=d.get)
    return dict(loss=max(steps), grad=grad[worst(grad)],
                delta=delta[worst(delta)],
                detail=dict(loss_steps=steps, grad_leaf=worst(grad),
                            delta_leaf=worst(delta), grad_gaps=grad,
                            delta_gaps=delta,
                            left_out=sorted(set(gnorm) - set(live))))
