"""The reduction from trace to device numbers, on synthetic intervals and
on a trimmed trace recorded on a TPU v5e."""
import gzip
import json
import os

import pytest

from chipbench import tracereduce as tr

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")


def _trace(device, host):
    return dict(device=device, host=host)


def test_union_merges_overlaps_and_touching():
    evs = [("a", 0, 10), ("b", 5, 12), ("c", 12, 15), ("d", 20, 25)]
    assert tr.union(evs) == [(0, 15), (20, 25)]
    assert tr.busy_ns(evs) == 20


def test_clip_to_window():
    evs = [["a", 0, 10], ["b", 95, 10], ["c", 200, 5]]
    assert tr.clip(evs, 5, 100) == [("a", 5, 10), ("b", 95, 100)]


def test_gaps_cover_the_idle_window():
    evs = [("a", 10, 20), ("b", 30, 40)]
    assert tr.gaps(evs, 0, 50) == [(0, 10), (20, 30), (40, 50)]
    assert tr.gaps([], 0, 50) == [(0, 50)]


def test_gap_labelled_by_innermost_covering_span():
    host = [["chipbench.window", 0, 100], ["chipbench.dispatch", 0, 30],
            ["chipbench.wait", 30, 70], ["other", 0, 100]]
    assert tr.label_gap((5, 25), host) == "dispatch"
    assert tr.label_gap((40, 60), host) == "wait"
    assert tr.label_gap((200, 210), host) == "untraced"


def test_reduce_synthetic_two_devices():
    ms = 1_000_000
    trace = _trace(
        device={"/device:TPU:0": [["fusion.1", 0, 4 * ms],
                                  ["fusion.2", 6 * ms, 4 * ms]],
                "/device:TPU:1": [["fusion.1", 0, 8 * ms]]},
        host=[["chipbench.window", 0, 10 * ms],
              ["chipbench.wait", 3 * ms, 4 * ms]])
    red = tr.reduce(trace, frozenset({"fusion.2"}))
    assert red["devices"] == 2
    assert red["window_s"] == pytest.approx(0.010)
    assert red["busy_s"] == pytest.approx((0.008 + 0.008) / 2)
    assert red["agg_s"] == pytest.approx(0.004 / 2)
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx((0.004 + 0.008) / 2)
    gaps = red["breakdown"]["idle_gaps"]
    assert gaps[0][0] == "wait" and gaps[0][1] == pytest.approx(0.002)
    assert len(gaps) == 2


def test_reduce_needs_one_window_and_a_device():
    with pytest.raises(ValueError):
        tr.reduce(_trace({"/device:TPU:0": []}, []), frozenset())
    with pytest.raises(ValueError):
        tr.reduce(_trace({}, [["chipbench.window", 0, 1]]), frozenset())


HLO = """HloModule jit_step

%fused_gather (p0: f32[8,4], p1: s32[3]) -> f32[3,4] {
  %p0 = f32[8,4]{1,0} parameter(0)
  %p1 = s32[3]{0} parameter(1)
  ROOT %gather.1 = f32[3,4]{1,0} gather(%p0, %p1), offset_dims={1}
}

%fused_mul (p0: f32[3,4]) -> f32[3,4] {
  %p0 = f32[3,4]{1,0} parameter(0)
  ROOT %multiply.2 = f32[3,4]{1,0} multiply(%p0, %p0)
}

%outer (p0: f32[8,4], p1: s32[3]) -> f32[3,4] {
  %p0 = f32[8,4]{1,0} parameter(0)
  %p1 = s32[3]{0} parameter(1)
  ROOT %inner = f32[3,4]{1,0} fusion(%p0, %p1), kind=kLoop, calls=%fused_gather
}

ENTRY %main (a: f32[8,4], i: s32[3]) -> (f32[3,4], f32[3,4]) {
  %a = f32[8,4]{1,0} parameter(0)
  %i = s32[3]{0} parameter(1)
  %fusion.7 = f32[3,4]{1,0} fusion(%a, %i), kind=kLoop, calls=%outer
  %fusion.8 = f32[3,4]{1,0} fusion(%fusion.7), kind=kLoop, calls=%fused_mul
  %scatter-add.3 = f32[8,4]{1,0} scatter(%a, %i, %fusion.8), to_apply=%add
  %custom-call.4 = f32[3,4]{1,0} custom-call(%a), custom_call_target="tpu_custom_call"
  ROOT %t = (f32[3,4]{1,0}, f32[3,4]{1,0}) tuple(%fusion.7, %fusion.8)
}
"""


def test_hlo_agg_ops_classifies_gathers_scatters_and_kernels():
    ops = tr.hlo_agg_ops(HLO)
    assert {"fusion.7", "scatter-add.3", "custom-call.4"} <= ops
    assert "fusion.8" not in ops and "t" not in ops
    assert tr.is_agg("%fusion.7", ops) and not tr.is_agg("fusion.8", ops)


def _recorded():
    name = os.path.join(DATA, "gcn2x16-reddit.train")
    with gzip.open(name + ".events.json.gz", "rt") as f:
        trace = json.load(f)
    with gzip.open(name + ".step.hlo.txt.gz", "rt") as f:
        hlo = f.read()
    return trace, hlo


def test_recorded_trace_gcn_two_steps():
    """Two steps of gcn2x16-reddit.train on one TPU v5e (trimmed from a
    traced run): the row gathers and scatter-adds are ~89% of the device
    time, the device idles ~0.6%, in the waits between steps."""
    trace, hlo = _recorded()
    ops = tr.hlo_agg_ops(hlo)
    red = tr.reduce(trace, ops)
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(0.745927852)
    assert red["busy_s"] == pytest.approx(0.74112853)
    assert red["agg_s"] == pytest.approx(0.662144933)
    top = [name.split()[0] for name, _ in red["breakdown"]["device_ops"]]
    # the two backward scatter-adds, then the two forward row gathers
    assert top[:4] == ["fusion.8", "fusion.7", "fusion.10", "fusion.9"]
    assert tr.is_agg("%fusion.7 = f32[232966,16]{1,0} fusion(...)", ops)
    assert {"fusion.7", "fusion.8", "fusion.9", "fusion.10",
            "sort.2"} <= ops
    assert not any(n.startswith("convolution") for n in ops)
    assert [g[0] for g in red["breakdown"]["idle_gaps"]][:2] == ["wait",
                                                                "wait"]
