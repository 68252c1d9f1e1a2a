"""The layer names the compiled GNN step carries (``jax.named_scope``).

A scope is metadata only: it lands in each HLO instruction's
``op_name`` (``jit(step)/jvp(mgg.aggregate)/…/gather``, and
``transpose(jvp(mgg.aggregate))`` for the backward pass) and changes
neither the compiled code nor any number.  A profile or a compiled
program is then attributed to layers by name, whatever ops implement
them.  The names are kept here, once:

=================== =========================================================
``mgg.aggregate``   everything that computes Â·x: the engine's aggregations
                    and their D^-1/2 / D^-1 scalings, the ring bodies (their
                    fused ``·W`` included), the streamed bodies, the sampled
                    block sum
``mgg.ring``        each ring rotation (``lax.ppermute``), inside
                    ``mgg.aggregate``
``gnn.update``      the dense transforms of the model stages: matmul, bias,
                    activation
``gnn.loss``        ``masked_cross_entropy``
``train.optimizer`` ``adamw_update``
=================== =========================================================
"""
from __future__ import annotations

import functools

import jax

AGGREGATE = "mgg.aggregate"
RING = "mgg.ring"
UPDATE = "gnn.update"
LOSS = "gnn.loss"
OPTIMIZER = "train.optimizer"
SCOPES = (AGGREGATE, RING, UPDATE, LOSS, OPTIMIZER)


def scoped(name: str):
    """Decorator: trace the function under ``jax.named_scope(name)``.

    The scope opens at each call, so it covers whatever trace the call
    runs in.  A top-level ``jax.jit`` starts a fresh name stack, so a
    jitted body that is called eagerly carries its own scope."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
