"""The reduction from a profiler trace to the per-layer numbers: on an
extract of a trace recorded on a TPU v5e, on a hand-made trace in the
same layout (a ``/device:TPU:<n>`` plane with an ``XLA Modules`` line,
the harness's spans on the host plane), and on hand-made intervals."""
from __future__ import annotations

import pathlib

import pytest

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
    assert r["batches_unseen"] == 0


def test_a_batch_past_the_traces_last_device_event_is_unseen():
    # a second batch at 8000..10500 ns, inside the window, in which the
    # trace holds no device program: the device's events stop before it
    from jax.profiler import ProfileData
    late = HAND_MADE.replace(
        "events { metadata_id: 3 offset_ps: 4500000 duration_ps: 500000 } }",
        "events { metadata_id: 3 offset_ps: 4500000 duration_ps: 500000 }\n"
        "    events { metadata_id: 2 offset_ps: 7000000 duration_ps: 2500000 }"
        " }")
    r = trace.reduce(ProfileData.from_text_proto(late))
    assert r["batches_unseen"] == 1 and r["busy_ns"] == 4000


# the traced tail of the tiny BFS cell with the program's scopes and spans
# (test_stages.py): its XLA Ops line keeps only the first 500 ops, the
# first of its nine runs of the device loop and none of the others
SCOPED_TRACE = CHIP_TRACE.with_name("tiny_bfs_v5e_scoped.textproto")
# the extract's bench.traced span, and the same span ended 5 us after the
# first batch's bench.run_increment (at 178,992,498,000 ps)
WHOLE_TAIL = "duration_ps: 721383926000"
FIRST_BATCH = "duration_ps: 84489304000"
# the first loop's outer while (while.207, 74.95 of its 75.09 ms)
OUTER_WHILE = ("    events {\n      metadata_id: 718\n"
               "      offset_ps: 102325723000\n"
               "      duration_ps: 74954846328\n    }\n")


def first_batch_of_scoped_extract() -> str:
    """The scoped extract's text with the traced tail cut to its first
    batch, whose loop the extract's ops cover whole."""
    text = SCOPED_TRACE.read_text()
    assert text.count(WHOLE_TAIL) == 1 and text.count(OUTER_WHILE) == 1
    return text.replace(WHOLE_TAIL, FIRST_BATCH)


@pytest.mark.parametrize("whole, drop_outer, cut", [
    (False, False, 0),   # one batch: its loop's ops are all there
    (True, False, 8),    # nine: the ops stop within the first loop
    (False, True, 1),    # one, its outer while lost: a late start
])
def test_a_run_of_the_loop_whose_ops_the_trace_lost_is_cut(
        whole, drop_outer, cut):
    from jax.profiler import ProfileData
    text = SCOPED_TRACE.read_text() if whole else \
        first_batch_of_scoped_extract()
    if drop_outer:
        text = text.replace(OUTER_WHILE, "")
    r = trace.reduce(ProfileData.from_text_proto(text))
    assert r["loops_cut"] == cut and r["batches_unseen"] == 0


WHILE = "%while.3 = (s32[]) while((s32[]) %t), condition=%c, body=%b"
INNER = "%while.4 = (s32[]) while((s32[]) %u), condition=%d, body=%e"
FUSION = "%fusion.5 = s32[8] fusion(s32[8] %p), kind=kLoop, calls=%f"


@pytest.mark.parametrize("program, ops, cut", [
    # the outer while spans the program but for 0.17%: whole
    (100e6, [(0.1e6, 99.93e6, WHILE), (2e6, 30e6, INNER)], 0),
    # no while at all, or only ops that are not one
    (100e6, [], 1),
    (100e6, [(1e6, 99.9e6, FUSION)], 1),
    # the longest while falls 2% short of a 100 ms program: a late start
    # (the chip's case: the inner while 2.269 s of a 2.314 s program)
    (100e6, [(2e6, 100e6, INNER)], 1),
    # 0.5 ms short of a 20 ms program: within the 1 ms floor
    (20e6, [(0, 19.5e6, WHILE)], 0),
    # a while of another program does not count
    (100e6, [(101e6, 200e6, WHILE)], 1),
])
def test_loops_cut_holds_each_loop_to_its_outer_while(program, ops, cut):
    assert trace.loops_cut([(0.0, program)], ops) == cut
