"""Mosaic compiles the main-path Pallas kernels for a v5e chip.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests need no TPU: they lower and compile
each kernel at reddit's feature width (602, padded to 640) for one chip of
a described ``v5e:2x2`` host and check that the program holds the kernel
(``tpu_custom_call``).  Interpret mode cannot catch what they catch: block
shapes off Mosaic's (8, 128) tiling, and scalar-prefetched tables larger
than SMEM.  Nothing runs, so nothing here says anything about results or
speed.

The same fixture compiles a tiny GCN training step for the described chip,
to check that the TPU compiler's fusions keep the program's layer scopes
(``repro.obs.scopes``) on the ops that carry each layer's time.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

import repro.core as C
from chipbench.scopes import computations, hlo_scopes, matmul_ops, scope_of
from repro.kernels import neighbor_agg, ops
from repro.obs import scopes as S
from repro.train import optimizer as O

D = 602               # reddit's feature width
T = 4096              # rows of the gathered table
P, PS = 40_000, 16    # flat, one call's (P·ps) int32 table is 2.4 MiB
BLOCKED_T = 12_284    # the most rows ops' VMEM guard admits at pb=4, db=128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip cannot be read back from the
        # persistent cache, so keep it out of the cache.
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# name -> (function, argument shapes, the Pallas kernel it must hold);
# interpret=False, since the CPU backend would otherwise pick interpret mode
KERNELS = {
    "neighbor_gather_sum": (
        lambda b, n, m: ops.neighbor_gather_sum(b, n, m, interpret=False),
        [((T, D), jnp.float32), ((P, PS), jnp.int32), ((P, PS), jnp.bool_)],
        "gather_sum_pipelined"),
    "sparse_neighbor_gather_sum": (
        lambda v, i, n, m: ops.sparse_neighbor_gather_sum(
            v, i, n, m, d_feat=D, interpret=False),
        [((T, D // 4), jnp.float32), ((T, D // 4), jnp.int32),
         ((P, PS), jnp.int32), ((P, PS), jnp.bool_)],
        "sparse_gather_sum"),
    "gather_rows": (
        lambda s, i: ops.gather_rows(s, i, interpret=False),
        [((T, D), jnp.float32), ((P * PS,), jnp.int32)],
        "gather_rows"),
    # at the guard's edge: the blocked kernel, not a swap to the pipelined
    "neighbor_gather_sum_blocked": (
        lambda b, n, m: ops.neighbor_gather_sum(b, n, m, pb=4,
                                                interpret=False),
        [((BLOCKED_T, D), jnp.float32), ((P, PS), jnp.int32),
         ((P, PS), jnp.bool_)],
        "gather_sum_blocked"),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes, kernel = KERNELS[name]
    assert P * PS > neighbor_agg.SMEM_WORDS   # the tables go in chunks
    compiled = jax.jit(fn).lower(
        *(_spec(one_chip, s, d) for s, d in shapes)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert f"{kernel}/pallas_call" in text


def test_unchunked_table_exceeds_smem(one_chip, monkeypatch):
    """One call holding the whole (P·ps) table is refused: the reason the
    tables are chunked."""
    monkeypatch.setattr(neighbor_agg, "SMEM_WORDS", 2 * P * PS)
    unchunked = functools.partial(neighbor_agg.gather_sum_pipelined_call,
                                  db=128)
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED|smem|SMEM"):
        jax.jit(unchunked).lower(
            _spec(one_chip, (T, 640)), _spec(one_chip, (P, PS), jnp.int32),
            _spec(one_chip, (P, PS), jnp.int32)).compile()


# ---------------------------------------------------------------------------
# the layer scopes of a GCN step, through the TPU compiler's fusions
# ---------------------------------------------------------------------------

_ENTRY_OP = re.compile(r"^\s*(?:ROOT\s+)?%(\S+) = (\(.*?\)|\S+) "
                       r"([a-z][a-z0-9\-]*)\(")
_DIMS = re.compile(r"[a-z][a-z0-9]*\[([\d,]*)\]")
D_IN, CLASSES = 24, 5   # GCN 2 x 16: d_in >= 16, so layer 0 updates first


def _tiny_graph():
    return C.power_law(384, avg_degree=12.0, locality=0.3, seed=1)


def _scatter_update_rows(hlo: str):
    """Rows (all dimensions but the last) of each scatter's update operand,
    in every computation of the program."""
    out = []
    for instrs in computations(hlo).values():
        dims = {name: _DIMS.match(rest) for name, _, rest in instrs}
        for name, op, rest in instrs:
            if op == "scatter":
                upd = re.search(r"scatter\(([^)]*)\)", rest).group(1)
                shape = dims[upd.split(", ")[-1].lstrip("%")].group(1)
                out.append(int(np.prod([int(d) for d in shape.split(",")][:-1])))
    return out


def _entry_ops(hlo: str):
    """(name, result shape, opcode, op_name, line) of the entry's ops."""
    entry = hlo[hlo.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    out = []
    for line in entry.splitlines()[1:]:
        m = _ENTRY_OP.match(line)
        if m:
            meta = re.search(r'op_name="([^"]*)"', line)
            out.append((*m.groups(), meta.group(1) if meta else "", line))
    return out


@pytest.fixture(scope="module")
def gcn_step_hlo(one_chip):
    """HLO of a GCN 2 x 16 training step at a tiny size, compiled for one
    v5e: forward, backward, masked cross-entropy and AdamW."""
    (dev,) = one_chip.device_set
    mesh = Mesh(np.array([dev]), ("ring",))
    eng = C.GNNEngine.build(_tiny_graph(), mesh, ps=16, dist=2)
    init, apply, kw = C.MODEL_ZOO["gcn"]
    params = jax.eval_shape(lambda k: init(k, D_IN, CLASSES, **kw),
                            jax.random.key(0))
    opt = jax.eval_shape(O.adamw_init, params)
    rep = NamedSharding(mesh, PartitionSpec())
    rows = NamedSharding(mesh, PartitionSpec("ring"))
    shaped = lambda tree, sh: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), tree)
    n = eng.plan.rows_per_dev
    cfg = O.AdamWConfig()

    def step(params, opt, xp, yp, mp):
        loss, grads = jax.value_and_grad(lambda p: C.masked_cross_entropy(
            apply(p, eng, xp), yp, mp))(params)
        params, opt, _ = O.adamw_update(grads, opt, params, cfg)
        return params, opt, loss

    return n, jax.jit(step).lower(
        shaped(params, rep), shaped(opt, rep),
        jax.ShapeDtypeStruct((n, D_IN), jnp.float32, sharding=rows),
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=rows),
        jax.ShapeDtypeStruct((n,), jnp.float32, sharding=rows),
    ).compile().as_text()


def test_gcn_step_keeps_its_layer_scopes_on_v5e(gcn_step_hlo):
    """GCN 2 x 16 at a tiny size, compiled for one v5e: the scatter-adds,
    the row gathers and any (P, ps, D) relayout lie under
    ``mgg.aggregate`` (the label gather under ``gnn.loss``), and every
    updated parameter and moment comes out of ``train.optimizer``."""
    _, hlo = gcn_step_hlo
    ops_ = _entry_ops(hlo)

    def of(pred):
        return [(name, scope_of(on)) for name, shape, op, on, _ in ops_
                if pred(shape, op, on)]

    last = lambda on: on.rsplit("/", 1)[-1]
    scatters = of(lambda sh, op, on: op == "fusion"
                  and last(on) == "scatter-add")
    gathers = of(lambda sh, op, on: op == "fusion" and last(on) == "gather"
                 and "take_along_axis" not in on)
    label = of(lambda sh, op, on: "take_along_axis" in on)
    rank3 = lambda sh: re.match(r"f32\[\d+,\d+,\d+\]", sh) is not None
    relayouts = of(lambda sh, op, on: op == "copy" and rank3(sh))
    assert len(scatters) >= 4 and len(gathers) >= 4, (scatters, gathers)
    for name, scope in scatters + gathers + relayouts:
        assert scope == S.AGGREGATE, name
    assert label and all(scope == S.LOSS for _, scope in label)

    root = next(line for *_, line in ops_ if line.lstrip().startswith("ROOT"))
    outs = re.findall(r"%([\w.\-]+)", root.split(" tuple(", 1)[1])
    scope_at = {name: scope_of(on) for name, _, _, on, _ in ops_}
    *state, loss = outs                 # params', opt' ..., then the loss
    assert all(scope_at[o] == S.OPTIMIZER for o in state), \
        [(o, scope_at[o]) for o in state]
    assert scope_at[loss] == S.LOSS


def test_gcn_step_matmul_fusions_lie_under_the_update_on_v5e(gcn_step_hlo):
    """The v5e compiler fuses x.W1 with the aggregation's D^-1/2 scaling and
    W1's gradient with the optimizer's gradient norm.  The fusions that run
    those matmuls, and every other op that runs one, are attributed to
    ``gnn.update`` (``chipbench.scopes.hlo_scopes``), so the dense update's
    device time is read under its own name."""
    n, hlo = gcn_step_hlo
    scopes = hlo_scopes(hlo)
    matmuls = matmul_ops(hlo)
    shapes = {sh for found in matmuls.values() for sh in found}
    assert f"f32[{n},16]" in shapes          # x.W1 (and the hidden grad)
    assert f"f32[{D_IN},16]" in shapes       # W1's gradient
    assert f"f32[16,{CLASSES}]" in shapes    # W2's gradient
    assert all(scopes.get(name) == S.UPDATE for name in matmuls), \
        [(name, scopes.get(name)) for name in matmuls]


def test_gcn_step_backward_gathers_over_the_transposed_plan_on_v5e(
        gcn_step_hlo):
    """The aggregations' backward is the ring over the transposed plan: the
    step holds no scatter of the P·ps gathered rows (autodiff's transpose
    of the row gather), only the scatters of partition partials, and the
    backward's row gathers lie under ``mgg.aggregate``."""
    _, hlo = gcn_step_hlo
    g = _tiny_graph().with_self_loops()
    p_ps = C.build_plan(g, 1, ps=16, dist=2).local_nbrs.shape[1] * 16
    rows = _scatter_update_rows(hlo)
    assert len(rows) >= 4 and p_ps not in rows, (p_ps, rows)

    scopes = hlo_scopes(hlo)
    backward = [name for name, _, op, on, _ in _entry_ops(hlo)
                if op == "fusion" and on.rsplit("/", 1)[-1] == "gather"
                and "transpose(jvp(" in on]
    assert len(backward) >= 2, backward
    assert all(scopes.get(name) == S.AGGREGATE for name in backward), \
        [(name, scopes.get(name)) for name in backward]
