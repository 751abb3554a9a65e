"""The trace reduction, on a hand-made trace whose answers are known and on
a small trace recorded on a TPU v5e (``fixtures/small.xplane.pb``)."""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import xplane  # noqa: E402

FIXTURE = os.path.join(BENCH, "tests", "fixtures", "small.xplane.pb")

# times in microseconds from the line's start (1 ms); the window is
# 0-10,000 us.  Chip 0 runs a while loop 1,000-3,000 whose body is a
# scatter (1,000-2,000) and a fusion (2,000-3,000), then a gather
# 6,000-7,000; chip 1 runs one 4,000 us scatter.  The graph has 1,000
# edges: the scatter and the gather touch edge-length arrays, the fusion
# does not, and the while loop holds other ops.
EDGES = 1000
SCATTER = "%scatter.1 = f32[100]{0} scatter(s32[1000]{0} %i, f32[1000]{0} %v)"
FUSION = "%fusion.2 = f32[100]{0} fusion(f32[100]{0} %x), kind=kLoop"
GATHER = "%gather.3 = f32[1000]{0} gather(f32[100]{0} %v, s32[1000]{0} %i)"
WHILE = "%while.4 = (f32[100]{0}, s32[1000]{0}) while((f32[100]{0}) %t)"


def _meta(i, name):
    return (f'event_metadata {{ key: {i} value {{ id: {i} name: '
            f'"{name}" }} }}')


HAND = f"""
planes {{
  id: 1 name: "/host:CPU"
  lines {{ id: 1 name: "python3" timestamp_ns: 1000000
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 10000000000 }}
    events {{ metadata_id: 2 offset_ps: 800000000 duration_ps: 2700000000 }}
    events {{ metadata_id: 3 offset_ps: 3500000000 duration_ps: 6000000000 }}
  }}
  {_meta(1, "bench.window")} {_meta(2, "bench.job")}
  {_meta(3, "bench.wait_arrival")}
}}
planes {{
  id: 2 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000000
    events {{ metadata_id: 4 offset_ps: 1000000000 duration_ps: 2000000000 }}
    events {{ metadata_id: 1 offset_ps: 1000000000 duration_ps: 1000000000 }}
    events {{ metadata_id: 2 offset_ps: 2000000000 duration_ps: 1000000000 }}
    events {{ metadata_id: 3 offset_ps: 6000000000 duration_ps: 1000000000 }}
  }}
  {_meta(1, SCATTER)} {_meta(2, FUSION)} {_meta(3, GATHER)}
  {_meta(4, WHILE)}
}}
planes {{
  id: 3 name: "/device:TPU:1"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000000
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 4000000000 }}
  }}
  {_meta(1, SCATTER)}
}}
"""


def test_hand_made_trace():
    from jax.profiler import ProfileData

    s = xplane.summarize(ProfileData.from_text_proto(HAND), EDGES)
    assert s.devices == 2
    assert s.window_s == pytest.approx(0.010)
    # chip 0 busy 1,000-3,000 and 6,000-7,000 = 3 ms; chip 1 4 ms
    assert s.busy_s == pytest.approx(0.0035)
    # push ops: chip 0 scatter 1 ms + gather 1 ms, chip 1 scatter 4 ms;
    # the while loop holds ops, so it is no leaf and has no self time
    assert s.push_s == pytest.approx(0.003)
    assert s.push_ops == ["gather.3 gather", "scatter.1 scatter"]
    assert s.device_ops[0] == ["scatter.1 scatter", pytest.approx(0.0025)]
    assert s.op_s["while.4 while"] == pytest.approx(0.0)
    assert s.op_s["fusion.2 fusion kLoop"] == pytest.approx(0.0005)
    # chip 0's gaps: 0-1,000 (in no span but the window), 3,000-6,000
    # (mostly waiting for an arrival), 7,000-10,000 (waiting)
    names = [g[0] for g in s.idle_gaps]
    lengths = [g[1] for g in s.idle_gaps]
    assert lengths == pytest.approx([0.003, 0.003, 0.001])
    assert names[2] == "no host span"
    assert set(names[:2]) == {"bench.wait_arrival"}


def test_recorded_trace():
    # a scale-12 graph of 57,203 edges: PageRank (20 supersteps) and a
    # B=16 BFS run_batch, each once with the fused kernel and once staged
    s = xplane.summarize(FIXTURE, 57_203)
    assert s.devices == 1
    assert 0 < s.busy_s < s.window_s
    assert 0.9 * s.busy_s < s.push_s <= s.busy_s
    assert "push.10 custom-call tpu_custom_call" in s.push_ops
    assert "fusion.15 fusion kCustom" in s.push_ops
    assert not any(op.split()[1] == "while" for op in s.push_ops)
    assert len(s.device_ops) <= 10
    assert len(s.idle_gaps) <= 10
    assert {g[0] for g in s.idle_gaps} & {"bench.job", "bench.server.step",
                                          "bench.wait_arrival"}
