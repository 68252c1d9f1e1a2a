"""Device time by the program's layer scopes: the HLO attribution, the
reduction over a trace, the programs' HLO read back from a profile, the
readers, and the compiled step of a CPU-sized cell."""
import glob
import gzip
import importlib
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from chipbench import harness, peaks
from chipbench import scopes as sc
from chipbench import tracereduce as tr
from chipbench.tests.tiny import tiny_cell

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")

HLO = """HloModule jit_step

%fused_scatter (p0: f32[8,4], p1: s32[3], p2: f32[3,4]) -> f32[8,4] {
  %p0 = f32[8,4]{1,0} parameter(0)
  %p1 = s32[3]{0} parameter(1)
  %p2 = f32[3,4]{1,0} parameter(2)
  ROOT %scatter.1 = f32[8,4]{1,0} scatter(%p0, %p1, %p2), to_apply=%add, metadata={op_name="jit(step)/transpose(jvp(mgg.aggregate))/jit(_take)/scatter-add" stack_frame_id=3}
}

%fused_mixed (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %m.1 = f32[4]{0} multiply(%p0, %p0), metadata={op_name="jit(step)/jvp(gnn.update)/mul"}
  ROOT %m.2 = f32[4]{0} add(%m.1, %p0), metadata={op_name="jit(step)/train.optimizer/add"}
}

%fused_bare (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  ROOT %m.3 = f32[4]{0} negate(%p0)
}

%fused_outer (p0: f32[8,4], p1: s32[3], p2: f32[3,4]) -> f32[8,4] {
  %p0 = f32[8,4]{1,0} parameter(0)
  %p1 = s32[3]{0} parameter(1)
  %p2 = f32[3,4]{1,0} parameter(2)
  ROOT %inner = f32[8,4]{1,0} fusion(%p0, %p1, %p2), kind=kLoop, calls=%fused_scatter
}

%fused_dot_scale (p0: f32[8,4], p1: f32[4,2], p2: f32[8]) -> f32[8,2] {
  %p0 = f32[8,4]{1,0} parameter(0)
  %p1 = f32[4,2]{1,0} parameter(1)
  %p2 = f32[8]{0} parameter(2)
  %convolution.1 = f32[8,2]{1,0} convolution(%p0, %p1), dim_labels=bf_io->bf, metadata={op_name="jit(step)/jvp(gnn.update)/dot_general"}
  %b.1 = f32[8,2]{1,0} broadcast(%p2), dimensions={0}
  ROOT %mul.1 = f32[8,2]{1,0} multiply(%convolution.1, %b.1), metadata={op_name="jit(step)/jvp(mgg.aggregate)/mul"}
}

%fused_nested_dot (p0: f32[8,4], p1: f32[4,2], p2: f32[8]) -> f32[8,2] {
  %p0 = f32[8,4]{1,0} parameter(0)
  %p1 = f32[4,2]{1,0} parameter(1)
  %p2 = f32[8]{0} parameter(2)
  %inner.2 = f32[8,2]{1,0} fusion(%p0, %p1, %p2), kind=kOutput, calls=%fused_dot_scale
  ROOT %neg.2 = f32[8,2]{1,0} negate(%inner.2), metadata={op_name="jit(step)/train.optimizer/neg"}
}

%fused_two_dots (p0: f32[8,4], p1: f32[4,2]) -> f32[8,2] {
  %p0 = f32[8,4]{1,0} parameter(0)
  %p1 = f32[4,2]{1,0} parameter(1)
  %dot.3 = f32[8,2]{1,0} dot(%p0, %p1), metadata={op_name="jit(step)/jvp(gnn.update)/dot_general"}
  %dot.4 = f32[8,2]{1,0} dot(%p0, %p1), metadata={op_name="jit(step)/jvp(mgg.aggregate)/dot_general"}
  ROOT %add.5 = f32[8,2]{1,0} add(%dot.3, %dot.4), metadata={op_name="jit(step)/jvp(gnn.update)/add"}
}

ENTRY %main (a: f32[8,4], i: s32[3], u: f32[3,4], w: f32[4], w2: f32[4,2], d: f32[8]) -> f32[8,4] {
  %a = f32[8,4]{1,0} parameter(0), metadata={op_name="a"}
  %i = s32[3]{0} parameter(1)
  %u = f32[3,4]{1,0} parameter(2)
  %w = f32[4]{0} parameter(3)
  %w2 = f32[4,2]{1,0} parameter(4)
  %d = f32[8]{0} parameter(5)
  %gather.3 = f32[3,4]{1,0} gather(%a, %i), offset_dims={1}, metadata={op_name="jit(step)/jvp(mgg.aggregate)/mgg.aggregate/jit(_take)/gather"}
  %fusion.1 = f32[8,4]{1,0} fusion(%a, %i, %u), kind=kLoop, calls=%fused_scatter
  %fusion.2 = f32[4]{0} fusion(%w), kind=kLoop, calls=%fused_mixed
  %fusion.3 = f32[4]{0} fusion(%w), kind=kLoop, calls=%fused_bare
  %fusion.4 = f32[8,4]{1,0} fusion(%a, %i, %u), kind=kLoop, calls=%fused_outer
  %copy.4 = f32[3,4]{0,1} copy(%gather.3), metadata={op_name="jit(step)/transpose(jvp(mgg.aggregate))/mgg.aggregate/mul"}
  %dot.5 = f32[3]{0} dot(%u, %w), metadata={op_name="jit(step)/jvp(gnn.update)/dot_general"}
  %v.6 = f32[4]{0} negate(%w), metadata={op_name="jit(step)/vmap(gnn.loss)/neg"}
  %r.7 = f32[4]{0} negate(%w), metadata={op_name="jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/mgg.aggregate/mul"}
  %copy-start.8 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%w)
  %n.9 = f32[4]{0} negate(%w), metadata={op_name="jit(step)/jvp()/mul"}
  %o.10 = f32[4]{0} negate(%w), metadata={op_name="jit(step)/mgg.aggregates/add"}
  %p.11 = f32[4]{0} collective-permute(%w), metadata={op_name="jit(step)/jvp(mgg.aggregate)/mgg.ring/ppermute"}
  %p.12 = f32[4]{0} collective-permute(%w), metadata={op_name="jit(step)/mgg.ring/ppermute"}
  %s.13 = f32[4]{0} fusion(%w), kind=kLoop, calls=%fused_mixed, metadata={op_name="jit(step)/transpose(jvp(gnn.update))/mul"}
  %k.14 = f32[8,2]{1,0} fusion(%a, %w2, %d), kind=kOutput, calls=%fused_dot_scale, metadata={op_name="jit(step)/jvp(mgg.aggregate)/mul"}
  %k.15 = f32[8,2]{1,0} fusion(%a, %w2, %d), kind=kLoop, calls=%fused_nested_dot, metadata={op_name="jit(step)/train.optimizer/neg"}
  %k.16 = f32[8,2]{1,0} fusion(%a, %w2), kind=kOutput, calls=%fused_two_dots, metadata={op_name="jit(step)/jvp(gnn.update)/add"}
  ROOT %t = f32[8,4]{1,0} add(%fusion.1, %fusion.4)
}
"""


def test_hlo_scopes_unwrap_transforms_and_take_the_outermost():
    s = sc.hlo_scopes(HLO)
    assert s["gather.3"] == s["copy.4"] == s["r.7"] == sc.AGGREGATE
    assert s["dot.5"] == sc.UPDATE
    assert s["v.6"] == sc.LOSS
    assert s["p.11"] == sc.AGGREGATE      # the ring nested in aggregation
    assert s["p.12"] == sc.RING


def test_hlo_scopes_fusion_fallback_mixed_and_unscoped():
    s = sc.hlo_scopes(HLO)
    assert s["fusion.1"] == sc.AGGREGATE  # its fused scatter's scope
    assert s["fusion.4"] == sc.AGGREGATE  # through a nested fusion
    assert s["fusion.2"] == sc.MIXED      # update and optimizer inside
    assert s["s.13"] == sc.UPDATE         # its own op_name first
    for name in ("fusion.3", "copy-start.8", "n.9", "o.10", "t", "a"):
        assert name not in s


def test_hlo_scopes_a_fusion_takes_its_matmuls_scope():
    """A matmul fused with ops of another layer (the D^-1/2 scaling of the
    aggregation after x.W, say) is the fusion's work: the fusion takes the
    matmul's scope, whatever its own op_name or root says."""
    s = sc.hlo_scopes(HLO)
    assert s["k.14"] == sc.UPDATE         # root and op_name: mgg.aggregate
    assert s["k.15"] == sc.UPDATE         # the matmul in a nested fusion
    assert s["k.16"] == sc.MIXED          # matmuls of two layers
    assert s["inner.2"] == sc.UPDATE and s["mul.1"] == sc.AGGREGATE
    assert sc.matmul_ops(HLO) == {"dot.5": ["f32[3]"], "k.14": ["f32[8,2]"],
                                  "k.15": ["f32[8,2]"],
                                  "k.16": ["f32[8,2]", "f32[8,2]"]}


def _trace(device, host):
    return dict(device=device, host=host)


def test_reduce_scopes_sum_to_busy_time():
    ms = 1_000_000
    dev0 = [["%gather.3 = f32[3,4] gather(...)", 0, 4 * ms],
            ["%dot.5 = f32[3] dot(...)", 4 * ms, 1 * ms],
            ["%copy-start.8 = (...) copy-start(...)", 4 * ms, 3 * ms],
            ["%fusion.2 = f32[4] fusion(...)", 8 * ms, 4 * ms]]
    dev1 = [["%fusion.1 = f32[8,4] fusion(...)", 2 * ms, 2 * ms]]
    trace = _trace({"/device:TPU:0": dev0, "/device:TPU:1": dev1},
                   [["chipbench.window", 1 * ms, 10 * ms]])
    secs = sc.reduce_scopes(trace, sc.hlo_scopes(HLO))
    red = tr.reduce(trace, frozenset())
    assert sum(secs.values()) == pytest.approx(red["busy_s"])
    assert secs[sc.AGGREGATE] == pytest.approx((3e-3 + 2e-3) / 2)
    assert secs[sc.UPDATE] == pytest.approx(1e-3 / 2)
    # the async copy beside the dot counts from where the dot ends
    assert secs[sc.UNSCOPED] == pytest.approx(2e-3 / 2)
    assert secs[sc.MIXED] == pytest.approx(3e-3 / 2)      # clipped at 11 ms
    assert list(secs) == sorted(secs, key=lambda k: -secs[k])


def test_program_hlo_and_step_program_from_a_cpu_profile(tmp_path):
    """A profile keeps each program's HLO with its scopes; the window's
    device ops pick the program that ran, and reduce by its scopes."""
    def scoped_step(x, i):
        with jax.named_scope(sc.AGGREGATE):
            y = jnp.take(x, i, axis=0).sum(0)
        with jax.named_scope(sc.UPDATE):
            return jnp.tanh(y @ x.T).sum()

    f = jax.jit(jax.grad(scoped_step))
    x, i = jnp.ones((64, 64)), jnp.arange(256) % 64
    f(x, i).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            for _ in range(3):
                f(x, i).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    programs = sc.program_hlo(path)
    (key,) = [k for k in programs if k.startswith("jit_scoped_step(")]
    assert "jvp(mgg.aggregate)" in programs[key]
    assert "transpose(jvp(gnn.update))" in programs[key]

    # the CPU's ops, as the TPU's "XLA Ops" line would hold them
    events = [ev for plane in ProfileData.from_file(path).planes
              for line in plane.lines for ev in line.events]
    ops = [[ev.name, int(ev.start_ns), int(ev.duration_ns)] for ev in events
           if any(k == "hlo_op" for k, _ in ev.stats)]
    host = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
            for ev in events if ev.name == tr.WINDOW_SPAN]
    trace = _trace({"/device:CPU:0": ops}, host)
    assert sc.step_program(trace, programs) == key
    secs = sc.reduce_scopes(trace, sc.hlo_scopes(programs[key]))
    assert secs[sc.AGGREGATE] > 0 and secs[sc.UPDATE] > 0
    assert sum(secs.values()) == pytest.approx(
        tr.reduce(trace, frozenset())["busy_s"])


NEW = ["scope_agg_ms", "scope_agg_roofline", "scope_update_ms",
       "scope_optimizer_ms", "unscoped_share"]


def _ctx(secs):
    trace = dict(busy_s=sum(secs.values()), breakdown={})
    return dict(scopes=secs, steps=4, trace=trace,
                work=dict(agg_bytes=8.19e8),
                peak=peaks.peak("TPU v5 lite"))


def test_readers_read_the_scopes():
    ctx = _ctx({sc.AGGREGATE: 0.8, sc.UPDATE: 0.02, sc.OPTIMIZER: 0.004,
                sc.UNSCOPED: 0.016})
    read = {m: importlib.import_module(f"chipbench.metrics.{m}").read(ctx)
            for m in NEW}
    assert read["scope_agg_ms"] == pytest.approx(200.0)
    assert read["scope_agg_roofline"] == pytest.approx(
        100.0 * 1e-3 * 4 / 0.8)           # 1 ms of bytes a step at 819 GB/s
    assert read["scope_update_ms"] == pytest.approx(5.0)
    assert read["scope_optimizer_ms"] == pytest.approx(1.0)
    assert read["unscoped_share"] == pytest.approx(100.0 * 0.016 / 0.84)


def test_readers_read_nothing_from_a_program_that_names_no_scope():
    ctx = _ctx({})
    for m in NEW:
        assert importlib.import_module(
            f"chipbench.metrics.{m}").read(ctx) is None


def test_every_gather_scatter_and_dot_of_the_step_sits_in_a_scope():
    """The benchmark's compiled step, at a CPU size: each gather, scatter
    and dot names a layer, and so does each fusion that holds one; every
    op that runs a matmul is the dense update's."""
    cell = tiny_cell("gcn2x16-reddit.train")
    indptr, indices = harness.make_graph(cell.config, cell.traffic, 7)
    hlo = harness.build_program(cell, 7, indptr, indices).hlo
    scopes = sc.hlo_scopes(hlo)
    heavy = ("gather", "scatter", "dot")
    found = {op: 0 for op in heavy}
    for line in hlo.splitlines():
        m = tr._INSTR.match(line)
        op = tr._OPCODE.search(m.group(2)) if m else None
        if op and op.group(1) in heavy:
            found[op.group(1)] += 1
            meta = tr._OP_NAME.search(m.group(2))
            assert meta and sc.scope_of(meta.group(1)), line
    assert all(found.values()), found
    # the ops the device runs that the opcode classification counts: the
    # aggregation's, and the loss's label gather
    entry = hlo[hlo.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    ran = {m.group(1) for m in map(tr._INSTR.match, entry.splitlines()[1:])
           if m}
    agg = tr.hlo_agg_ops(hlo) & ran
    assert agg and all(scopes.get(name) in (sc.AGGREGATE, sc.LOSS)
                       for name in agg)
    assert {sc.AGGREGATE, sc.UPDATE, sc.LOSS, sc.OPTIMIZER} <= set(
        scopes.values())
    matmuls = sc.matmul_ops(hlo)
    assert matmuls and all(scopes.get(name) == sc.UPDATE
                           for name in matmuls), matmuls


def test_recorded_scoped_trace_gcn_two_steps():
    """Two steps of gcn2x16-reddit.train on one TPU v5e, recorded with the
    program's scopes (trimmed from a traced run): the scopes and
    ``unscoped`` sum to busy time; the aggregation is all but 0.1% of it,
    the backward relayouts and mask multiplies that the opcode
    classification misses included, the label gather not."""
    name = os.path.join(DATA, "gcn2x16-reddit.train.scoped")
    with gzip.open(name + ".events.json.gz", "rt") as f:
        trace = json.load(f)
    with gzip.open(name + ".step.hlo.txt.gz", "rt") as f:
        hlo = f.read()
    scopes = sc.hlo_scopes(hlo)
    secs = sc.reduce_scopes(trace, scopes)
    agg_ops = tr.hlo_agg_ops(hlo)
    red = tr.reduce(trace, agg_ops)
    steps, busy = 2, red["busy_s"]
    assert red["window_s"] == pytest.approx(0.537347892)
    assert sum(secs.values()) == pytest.approx(busy)
    assert secs[sc.AGGREGATE] == pytest.approx(0.532708213)
    # the bounds predicted before the recording: aggregation >= 90% of busy,
    # update <= 3 ms and optimizer <= 1 ms a step, unscoped <= 3%
    assert secs[sc.AGGREGATE] >= 0.9 * busy
    assert secs[sc.UPDATE] / steps <= 3e-3
    assert secs[sc.OPTIMIZER] / steps <= 1e-3
    assert secs[sc.UNSCOPED] <= 0.03 * busy
    for op in ("copy.46", "copy.47", "broadcast_multiply_fusion",
               "broadcast_multiply_fusion.1"):
        assert scopes[op] == sc.AGGREGATE and op not in agg_ops
    assert scopes["fusion.4"] == sc.LOSS and "fusion.4" in agg_ops
    # x.W1 and W1's gradient: fused with the aggregation's D^-1/2 scaling
    # and the optimizer's gradient norm, the matmuls keep them the update's
    matmuls = sc.matmul_ops(hlo)
    assert matmuls["convolution_multiply_fusion"] == ["f32[20000,16]"]
    assert matmuls["multiply_reduce_fusion.2"] == ["f32[602,16]"]
    assert all(scopes[name] == sc.UPDATE for name in matmuls), matmuls
    # what the name counts and the opcode does not: 62.9 ms a step
    assert (secs[sc.AGGREGATE] - red["agg_s"]) / steps == pytest.approx(
        0.062896, abs=1e-6)
