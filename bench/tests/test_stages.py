"""Device time by machine stage and idle time by program span
(``bench/stages.py``): on a hand-made trace in the layout of a TPU v5e
trace, on two extracts of v5e traces (a program without scopes or spans,
and one with them), and on hand-made HLO text."""
from __future__ import annotations

import json
import pathlib
import types

import pytest

from bench import harness, stages, trace

DATA = pathlib.Path(__file__).parent / "data"
# the unscoped extract that test_trace.py reads
CHIP_TRACE = DATA / "tiny_bfs_v5e.textproto"
# the traced tail of the same tiny BFS cell (8x8 grid, 1,000-edge
# batches) run on a TPU v5e with jax 0.9.0 by the program with its cca.*
# scopes and repro.* spans: every plane's name, the device plane's XLA
# Modules line whole and its first 500 XLA Ops, the host's bench.* and
# repro.* spans with their stats; and the op_name of each of those ops
# with one, from the compiled device loop's HLO text (the v5e's op events
# carry none of their own)
SCOPED_TRACE = DATA / "tiny_bfs_v5e_scoped.textproto"
SCOPED_NAMES = DATA / "tiny_bfs_v5e_scoped.op_names.json"
LOOP = "jit(_increment_device_loop)/while/body/while/body"
# the per-layer metrics of stages and host ingest, and the scope of each
STAGE_METRICS = {
    "hop_us_per_cycle.thru": "cca.hop",
    "park_us_per_cycle.thru": "cca.park",
    "staging_us_per_cycle.thru": "cca.staging",
    "phase0_us_per_cycle.thru": "cca.phase0",
    "io_us_per_cycle.thru": "cca.io",
    "quiescence_us_per_cycle.thru": "cca.quiescent",
}
HOST_INGEST = "host_ingest_ms_per_kedge.thru"


def read_stage_metrics(view) -> dict:
    """What the readers under ``bench/metrics/`` of the seven metrics read
    from ``view``, leaving out those that read nothing."""
    out = {n: harness.load_reader(n)(view)
           for n in [*STAGE_METRICS, HOST_INGEST]}
    return {k: v for k, v in out.items() if v is not None}


HAND_MADE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 8000000 }
    events { metadata_id: 2 offset_ps: 9500000 duration_ps: 200000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 8000000 }
    events { metadata_id: 4 offset_ps: 1100000 duration_ps: 2000000 }
    events { metadata_id: 5 offset_ps: 3100000 duration_ps: 1000000 }
    events { metadata_id: 6 offset_ps: 4100000 duration_ps: 500000 }
    events { metadata_id: 7 offset_ps: 4600000 duration_ps: 500000 }
    events { metadata_id: 4 offset_ps: 5100000 duration_ps: 1000000 }
    events { metadata_id: 10 offset_ps: 6100000 duration_ps: 200000 }
    events { metadata_id: 11 offset_ps: 6100000 duration_ps: 200000 }
    events { metadata_id: 8 offset_ps: 8500000 duration_ps: 900000 }
    events { metadata_id: 9 offset_ps: 9500000 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "jit__increment_device_loop(7)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_broadcast_in_dim(5)" } }
  event_metadata { key: 3 value { id: 3 name: "%while.7 = (s32[]{:T(128)}, s32[8]{0:T(128)}) while((s32[]{:T(128)}, s32[8]{0:T(128)}) %tuple.1), condition=%cond.1, body=%body.1" } }
  event_metadata { key: 4 value { id: 4 name: "%hop_fusion.1 = s32[8]{0:T(128)S(1)} fusion(s32[8]{0:T(128)} %p.1), kind=kLoop, calls=%fused_computation.1" } }
  event_metadata { key: 5 value { id: 5 name: "%park_fusion.2 = s32[8]{0:T(128)} fusion(s32[8]{0:T(128)} %p.2), kind=kLoop, calls=%fused_computation.2" } }
  event_metadata { key: 6 value { id: 6 name: "%copy.3 = s32[8]{0:T(128)} copy(s32[8]{0:T(128)S(1)} %hop_fusion.1)" } }
  event_metadata { key: 7 value { id: 7 name: "%reduce.4 = s32[]{:T(128)} reduce(s32[8]{0:T(128)} %p.3, s32[]{:T(128)} %c.1), dimensions={0}, to_apply=%add" } }
  event_metadata { key: 8 value { id: 8 name: "%stage_fusion.5 = s32[8]{0:T(128)} fusion(s32[8]{0:T(128)} %p.4), kind=kLoop, calls=%fused_computation.5" } }
  event_metadata { key: 9 value { id: 9 name: "%broadcast.9 = s32[8]{0:T(128)} broadcast(s32[]{:T(128)} %c.2), dimensions={}" } }
  event_metadata { key: 10 value { id: 10 name: "%conditional.2 = (s32[]{:T(128)}) conditional(pred[]{:T(512)} %p.5, s32[]{:T(128)} %p.6), branch_computations={%b.1, %b.2}" } }
  event_metadata { key: 11 value { id: 11 name: "%phase_fusion.6 = s32[]{:T(128)} fusion(s32[]{:T(128)} %p.7), kind=kLoop, calls=%fused_computation.6" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 3 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 12000000 }
    events { metadata_id: 2 offset_ps: 200000 duration_ps: 10800000 }
    events { metadata_id: 3 offset_ps: 300000 duration_ps: 10500000
             stats { metadata_id: 1 int64_value: 4 }
             stats { metadata_id: 2 int64_value: 100 } }
    events { metadata_id: 4 offset_ps: 300000 duration_ps: 600000
             stats { metadata_id: 1 int64_value: 4 } }
    events { metadata_id: 5 offset_ps: 300000 duration_ps: 100000
             stats { metadata_id: 1 int64_value: 4 } }
    events { metadata_id: 6 offset_ps: 800000 duration_ps: 100000
             stats { metadata_id: 1 int64_value: 4 } }
    events { metadata_id: 7 offset_ps: 950000 duration_ps: 50000
             stats { metadata_id: 1 int64_value: 4 } }
    events { metadata_id: 8 offset_ps: 1000000 duration_ps: 8300000
             stats { metadata_id: 1 int64_value: 4 } } }
  event_metadata { key: 1 value { id: 1 name: "bench.traced" } }
  event_metadata { key: 2 value { id: 2 name: "bench.run_increment" } }
  event_metadata { key: 3 value { id: 3 name: "repro.increment" } }
  event_metadata { key: 4 value { id: 4 name: "repro.load_stream" } }
  event_metadata { key: 5 value { id: 5 name: "repro.load_stream.fetch" } }
  event_metadata { key: 6 value { id: 6 name: "repro.load_stream.upload" } }
  event_metadata { key: 7 value { id: 7 name: "repro.dispatch" } }
  event_metadata { key: 8 value { id: 8 name: "repro.wait" } }
  stat_metadata { key: 1 value { id: 1 name: "inc" } }
  stat_metadata { key: 2 value { id: 2 name: "edges" } } }
"""
# the op_name of each op, as the compiled HLO gives it (copy.3 has none)
NAMES = {"while.7": ("jit(_increment_device_loop)/while", set()),
         "hop_fusion.1": (f"{LOOP}/cca.hop/add", {"cca.hop"}),
         "park_fusion.2": (f"{LOOP}/cca.park/select", {"cca.park"}),
         "reduce.4": ("jit(_increment_device_loop)/while/body/while/cond/"
                      "cca.quiescent/reduce_sum", {"cca.quiescent"}),
         "stage_fusion.5": (f"{LOOP}/cca.staging/or", {"cca.staging"}),
         "phase_fusion.6": (f"{LOOP}/cca.chunk/cca.phase0/min",
                            {"cca.phase0"})}


def _hand_made():
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(HAND_MADE)


def test_hand_made_trace_reduces_to_known_stages_and_idle_spans():
    r = stages.reduce(_hand_made(), op_names=NAMES)
    # leaf ops inside the loop's program 1000..9000 ns: the while and the
    # conditional hold others and are left out; stage_fusion.5 is cut at
    # the program's end; broadcast.9 runs in another program
    assert r["stages"] == {"cca.hop": 3000, "cca.park": 1000,
                           "unattributed": 500, "cca.quiescent": 500,
                           "cca.phase0": 200, "cca.staging": 500}
    assert r["loop_ops_ns"] == 5700
    assert r["ops"][0] == ["hop_fusion.1", "cca.hop", 3e-6]
    # gaps: 0..1000 under load_stream (midpoint 500), 9000..9500 under
    # wait, 9700..12000 under run_increment (repro.increment ends 10800)
    assert r["idle_by_span"] == [["bench.run_increment", 2.3e-6],
                                 ["repro.load_stream", 1e-6],
                                 ["repro.wait", 5e-7]]
    assert r["span_ns"] == {"repro.increment": 10500,
                            "repro.load_stream": 600,
                            "repro.load_stream.fetch": 100,
                            "repro.load_stream.upload": 100,
                            "repro.dispatch": 50, "repro.wait": 8300}
    assert r["mixed_ns"] == 0
    assert set(stages.reduce(_hand_made())["stages"]) == {"unattributed"}
    # the old reduction of the same trace: its idle gaps stay bench.* only
    old = trace.reduce(_hand_made())
    assert [n for n, _ in old["idle_gaps"]] == ["bench.run_increment"]
    assert old["loop_ns"] == 8000


def test_fusions_of_two_stages_count_under_their_root_and_as_mixed():
    names = dict(NAMES, **{"copy.3": ("jit(f)/cca.io/copy", {"cca.io"}),
                           "hop_fusion.1": ("jit(f)/cca.hop/add",
                                            {"cca.hop", "cca.staging"})})
    r = stages.reduce(_hand_made(), op_names=names)
    assert r["stages"]["cca.io"] == 500 and "unattributed" not in r["stages"]
    assert r["stages"]["cca.hop"] == 3000 and r["mixed_ns"] == 3000


def _view(red, cycles, edges):
    batches = [dict(edges=edges, done=1.0, result=types.SimpleNamespace(
        cycles=cycles))]
    return types.SimpleNamespace(trace=red, window=dict(
        batches=batches, tail_from=0, tail_to=1))


def test_stage_metrics_read_per_machine_cycle_and_per_kedge():
    m = read_stage_metrics(_view(stages.reduce(_hand_made(), op_names=NAMES),
                                 cycles=10, edges=2000))
    assert m["hop_us_per_cycle.thru"] == pytest.approx(0.3)
    assert m["quiescence_us_per_cycle.thru"] == pytest.approx(0.05)
    assert m["host_ingest_ms_per_kedge.thru"] == pytest.approx(3e-4)
    # no op of the hand-made loop is of cca.io: a scope that the tail does
    # not hold reads nothing, not 0
    assert "io_us_per_cycle.thru" not in m and len(m) == 6
    assert read_stage_metrics(_view(None, 10, 2000)) == {}


@pytest.mark.parametrize("fused, want", [
    # cca.io's ops all fuse into hop_fusion.1, which cca.hop roots: the
    # loop holds the scope and its ops root no time, so it reads 0
    ({"cca.hop", "cca.io"}, 0.0),
    # no op of the loop carries cca.io: nothing to read (a misspelled or
    # removed scope fails the run)
    ({"cca.hop"}, None),
])
def test_a_scope_that_roots_no_op_reads_0_only_where_the_loop_holds_it(
        fused, want):
    names = dict(NAMES, **{"hop_fusion.1": (f"{LOOP}/cca.hop/add", fused)})
    red = stages.reduce(_hand_made(), op_names=names)
    assert "cca.io" not in red["stages"]
    assert ("cca.io" in red["scopes"]) == (want is not None)
    view = _view(red, cycles=10, edges=2000)
    assert stages.stage_us_per_cycle(view, "cca.io") == want
    assert harness.load_reader("io_us_per_cycle.thru")(view) == want
    assert stages.stage_us_per_cycle(view, "cca.hop") == pytest.approx(0.3)


# one 10 ms run of the loop whose ops stop for 4 ms in the middle: a block
# of events the trace dropped
DROPPED = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 10000000 duration_ps: 9980000000 }
    events { metadata_id: 4 offset_ps: 100000000 duration_ps: 1900000000 }
    events { metadata_id: 5 offset_ps: 6000000000 duration_ps: 3900000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit__increment_device_loop(7)" } }
  event_metadata { key: 3 value { id: 3 name: "%while.7 = (s32[]{:T(128)}) while((s32[]{:T(128)}) %tuple.1), condition=%cond.1, body=%body.1" } }
  event_metadata { key: 4 value { id: 4 name: "%hop_fusion.1 = s32[8]{0:T(128)S(1)} fusion(s32[8]{0:T(128)} %p.1), kind=kLoop, calls=%fused_computation.1" } }
  event_metadata { key: 5 value { id: 5 name: "%park_fusion.2 = s32[8]{0:T(128)} fusion(s32[8]{0:T(128)} %p.2), kind=kLoop, calls=%fused_computation.2" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 3 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.traced" } } }
"""


def test_a_block_of_dropped_ops_leaves_its_cycles_out_of_the_stages():
    from jax.profiler import ProfileData
    r = stages.reduce(ProfileData.from_text_proto(DROPPED), op_names=NAMES)
    # 2..6 ms holds no op: 40% of the loop's time; the 0.1 ms before the
    # first op and after the last are not a drop
    assert r["ops_lost_ns"] == 4e6 and r["longest_op_gap_ns"] == 4e6
    assert r["loop_seen_share"] == pytest.approx(0.6)
    view = _view(r, cycles=10, edges=2000)
    # 1.9 ms of hop over the 6 of 10 cycles the trace kept
    assert stages.stage_us_per_cycle(view, "cca.hop") == pytest.approx(
        1900 / 6)
    full = stages.reduce(_hand_made(), op_names=NAMES)
    assert full["ops_lost_ns"] == 0 and full["loop_seen_share"] == 1.0
    assert full["longest_op_gap_ns"] == 2200


def test_the_unscoped_extract_reduces_with_every_op_unattributed():
    from jax.profiler import ProfileData
    pd = ProfileData.from_text_proto(CHIP_TRACE.read_text())
    r = stages.reduce(pd)
    assert set(r["stages"]) == {"unattributed"} and r["loop_ops_ns"] > 0
    assert r["span_ns"] == {}
    old = trace.reduce(pd)
    assert r["idle_by_span"] == old["idle_gaps"]
    # a program without scopes or spans: the per-stage numbers read nothing
    assert read_stage_metrics(_view(r, cycles=10, edges=1000)) == {}


def test_opcode_reads_the_instruction_not_its_layouts():
    assert stages.opcode(
        "%while.207 = (f32[8,8,203,1]{3,2,1,0:T(8,128)}, s32[]{:T(128)}) "
        "while((f32[8,8,203,1]{3,2,1,0:T(8,128)}, s32[]{:T(128)}) "
        "%tuple.853), condition=%c, body=%b") == "while"
    assert stages.opcode(
        "%slice-start.20 = ((s32[8,8]{1,0:T(8,128)}), s32[2,8]{1,0:T(8,128)"
        "S(1)}, s32[]{:S(2)}) async-start(s32[8,8]{1,0:T(8,128)} %g), "
        "calls=%a") == "async-start"
    assert stages.opcode("jit__increment_device_loop(7)") is None
    assert stages.op_key("%copy-done.17 = s32[8]{0} copy-done(...)") \
        == "copy-done.17"


HLO = """HloModule jit__increment_device_loop, is_scheduled=true

%fused_computation.1 (param_0: s32[8]) -> s32[8] {
  %param_0 = s32[8]{0} parameter(0)
  %add.1 = s32[8]{0} add(s32[8]{0} %param_0, s32[8]{0} %param_0), metadata={op_name="jit(f)/while/body/cca.hop/add" stack_frame_id=2}
  ROOT %or.2 = s32[8]{0} or(s32[8]{0} %add.1, s32[8]{0} %param_0), metadata={op_name="jit(f)/while/body/cca.staging/or"}
}

ENTRY %main.9 (p: s32[8]) -> s32[8] {
  %p = s32[8]{0} parameter(0)
  %hop_fusion.1 = s32[8]{0} fusion(s32[8]{0} %p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/while/body/cca.staging/or"}
  ROOT %copy.3 = s32[8]{0} copy(s32[8]{0} %hop_fusion.1)
}
"""


def test_hlo_op_names_and_the_scopes_a_fusion_holds():
    names = stages.hlo_op_names(HLO)
    assert names["hop_fusion.1"] == ("jit(f)/while/body/cca.staging/or",
                                     {"cca.hop", "cca.staging"})
    assert names["copy.3"] == (None, set())
    assert names["add.1"][1] == {"cca.hop"}
    assert stages.scope_of("jit(f)/cca.chunk/cca.phase0/x") == "cca.phase0"
    assert stages.scope_of(None) == stages.UNATTRIBUTED


def test_scoped_chip_extract_reduces_to_every_stage_and_repro_spans():
    from jax.profiler import ProfileData
    pd = ProfileData.from_text_proto(SCOPED_TRACE.read_text())
    names = {k: (v, set())
             for k, v in json.loads(SCOPED_NAMES.read_text()).items()}
    bare, r = stages.reduce(pd), stages.reduce(pd, op_names=names)
    assert set(bare["stages"]) == {stages.UNATTRIBUTED}
    assert set(STAGE_METRICS.values()) <= set(r["stages"])
    assert r["loop_ops_ns"] == bare["loop_ops_ns"] > 0
    # every idle gap lies under a repro.* span; the old reduction names
    # them all bench.run_increment, and both add up to the same idle time
    old = trace.reduce(pd)
    assert old["n_ops"] == 500 and 0 < old["loop_ns"] < old["window_ns"]
    assert [n for n, _ in old["idle_gaps"]] == ["bench.run_increment"]
    assert all(n.startswith("repro.") for n, _ in r["idle_by_span"])
    assert sum(v for _, v in r["idle_by_span"]) == pytest.approx(
        sum(v for _, v in old["idle_gaps"]))
    # the spans of one increment share its inc
    spans = [(e.start_ns, e.end_ns, e.name, dict(e.stats))
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("repro.")]
    incs = [s for s in spans if s[2] == "repro.increment"]
    assert len(incs) >= 2
    for s0, e0, _, st in incs:
        inside = [s[3]["inc"] for s in spans if s0 <= s[0] and s[1] <= e0]
        assert len(inside) >= 7 and set(inside) == {st["inc"]}
