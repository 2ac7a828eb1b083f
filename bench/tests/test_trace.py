"""The reduction from a profiler trace to the per-layer numbers: on an
extract of a trace recorded on a TPU v5e, on a hand-made trace in the
same layout (a ``/device:TPU:<n>`` plane with an ``XLA Modules`` line,
the harness's spans on the host plane), and on hand-made intervals."""
from __future__ import annotations

import pathlib

from bench import trace

# the traced tail of the tiny BFS cell of test_cells.py (8x8 grid, one
# 1,000-edge batch) run on a TPU v5e with jax 0.9.0: every plane's name,
# the device plane's XLA Modules line whole and its first 200 XLA Ops, the
# host's bench.* spans, as a text proto
CHIP_TRACE = pathlib.Path(__file__).parent / "data" / "tiny_bfs_v5e.textproto"


def test_chip_trace_extract_reduces():
    from jax.profiler import ProfileData
    r = trace.reduce(ProfileData.from_text_proto(CHIP_TRACE.read_text()))
    assert r is not None and r["n_modules"] == 28 and r["n_ops"] == 200
    assert 0 < r["loop_ns"] <= r["busy_ns"] < r["window_ns"]
    assert r["busy_ns"] / r["window_ns"] > 0.5
    assert all(" = " not in name for name, _ in r["device_ops"])
    assert [n for n, _ in r["idle_gaps"]] == ["bench.run_increment"]


def test_union_clips_to_the_window_and_merges_overlaps():
    ivs = [(0, 5, "a"), (8, 12, "b"), (9, 10, "b.op"), (11, 15, "c"),
           (30, 40, "outside")]
    busy, gaps = trace.union_ns(ivs, 2, 20)
    assert busy == (5 - 2) + (15 - 8)
    assert gaps == [(5, 8), (15, 20)]
    assert trace.union_ns([], 0, 10) == (0.0, [(0, 10)])


def test_innermost_span_names_a_gap():
    spans = [(0, 100, "bench.run_increment"), (40, 60, "bench.mq_fold")]
    assert trace._innermost(spans, 50) == "bench.mq_fold"
    assert trace._innermost(spans, 20) == "bench.run_increment"
    assert trace._innermost(spans, 200) == trace.WINDOW_SPAN


HAND_MADE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 20000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit__increment_device_loop" } }
  event_metadata { key: 2 value { id: 2 name: "jit_convert_element_type" } } }
planes { id: 2 name: "/device:TPU:1"
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit__increment_device_loop" } } }
planes { id: 3 name: "/host:CPU"
  lines { id: 3 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 4500000 duration_ps: 500000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.traced" } }
  event_metadata { key: 2 value { id: 2 name: "bench.run_increment" } }
  event_metadata { key: 3 value { id: 3 name: "bench.mq_fold" } } }
"""


def test_hand_made_trace_reduces_to_known_numbers():
    from jax.profiler import ProfileData
    r = trace.reduce(ProfileData.from_text_proto(HAND_MADE))
    # window 1000..11000 ns on chip 0: programs at 2000..5000 and
    # 6000..7000 (the one at 21000 lies outside; chip 1 is not read)
    assert r["window_ns"] == 10_000 and r["busy_ns"] == 4000
    assert r["loop_ns"] == 3000 and r["n_modules"] == 3
    assert r["device_ops"] == [["jit__increment_device_loop", 3e-6],
                               ["jit_convert_element_type", 1e-6]]
    # gaps: 1000..2000 under run_increment, 5000..6000 under mq_fold
    # (innermost at 5500), 7000..11000 under the window alone
    assert r["idle_gaps"] == [["bench.traced", 4e-6],
                              ["bench.run_increment", 1e-6],
                              ["bench.mq_fold", 1e-6]]
