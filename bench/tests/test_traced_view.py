"""The traced view that per-layer readers get: the harness's run on the
CPU at a tiny grid, fed the traced tail of a v5e extract in place of the
CPU's trace (which has no TPU device plane), holds the device time of
every ``cca.*`` scope found in it; a declared metric that reads nothing
still fails the run; and a new stream kind and a reader of a new scope
enter the benchmark as new files and new entries only."""
from __future__ import annotations

import contextlib
import hashlib
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import pytest

from bench import harness, stages, trace
from test_cells import tiny_cell
from test_trace import first_batch_of_scoped_extract

DATA = pathlib.Path(__file__).parent / "data"
# the traced tail of the tiny BFS cell on a TPU v5e, with the op_name of
# each of its ops (test_stages.py)
SCOPED_TRACE = DATA / "tiny_bfs_v5e_scoped.textproto"
SCOPED_NAMES = DATA / "tiny_bfs_v5e_scoped.op_names.json"
BFS = "sbm50k-bfs.inc100k"


def chip_extract(whole: bool = False):
    """The v5e extract, its tail cut to the first batch (whose loop its
    ops cover whole) unless ``whole``."""
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(
        SCOPED_TRACE.read_text() if whole
        else first_batch_of_scoped_extract())


@pytest.fixture
def chip_trace(monkeypatch):
    """A traced run reads the v5e extract, and the op names of its device
    loop, in place of its own trace and HLO text; returns the names."""
    names = {k: (v, set())
             for k, v in json.loads(SCOPED_NAMES.read_text()).items()}

    @contextlib.contextmanager
    def traced(box):
        box[0] = "the v5e extract"
        yield
    monkeypatch.setattr(harness, "traced", traced)
    monkeypatch.setattr(harness, "read_trace", lambda d: chip_extract())
    monkeypatch.setattr(stages, "loop_op_names", lambda *a: names)
    return names


def test_traced_view_holds_the_stage_reduction_of_the_chip_extract(
        chip_trace):
    keep = {}
    out = harness.run_cell(tiny_cell(BFS), 7, 1.5, True, time.perf_counter(),
                           keep=keep)
    view = keep["trace"]
    want = stages.reduce(chip_extract(), op_names=chip_trace)
    assert view["stages"] == want["stages"]
    assert view["span_ns"] == want["span_ns"]
    assert view["loop_ops_ns"] == sum(view["stages"].values()) > 0
    assert view["mixed_ns"] == want["mixed_ns"]
    assert view["idle_by_span"] == want["idle_by_span"]
    old = trace.reduce(chip_extract())
    assert {k: view[k] for k in old} == old
    assert out["correct"] and out["problems"] == []
    declared = {m["name"] for m in harness.load_cell(BFS).per_layer}
    assert set(out["metrics"]) == declared and len(declared) == 10
    assert out["breakdown"]["idle_by_span"] == want["idle_by_span"][:10]


def test_a_declared_metric_that_reads_nothing_fails_the_run(
        chip_trace, monkeypatch, capsys):
    # the device loop's HLO names no op: no op carries a scope, so the
    # stage metrics that the cell declares read nothing
    from bench import run
    monkeypatch.setattr(stages, "loop_op_names", lambda *a: {})
    cell = tiny_cell(BFS)
    monkeypatch.setattr(harness, "load_cell", lambda name: cell)
    monkeypatch.setattr(harness, "start_jax", lambda chips: dict(
        platform="cpu", kind="cpu", count=1))
    rc = run.main(["--workload", BFS, "--seed", "7", "--seconds", "1.5",
                   "--trace", "1"])
    got = capsys.readouterr()
    assert rc != 0 and got.out == ""
    assert "metrics that read nothing" in got.err
    assert "hop_us_per_cycle.thru" in got.err


# a stream kind that no configuration has: distinct directed edges drawn
# uniformly, cut into equal increments, all from the graph's seed
NEW_KIND = '''"""Stream kind ``uniform``: distinct edges drawn uniformly."""
import numpy as np


def increments(graph):
    rng = np.random.default_rng(int(graph["seed"]))
    v = graph["n_vertices"]
    keys = rng.permutation(v * v)
    keys = keys[keys // v != keys % v][: graph["n_edges"]]
    edges = np.stack([keys // v, keys % v], axis=1).astype(np.int32)
    return np.array_split(edges, graph["increments"])
'''
# a reader of a scope that no metric reads yet
NEW_READER = '''"""Device microseconds of scope ``cca.rhizome_bcast`` per cycle."""
from bench.stages import stage_us_per_cycle


def read(view):
    return stage_us_per_cycle(view, "cca.rhizome_bcast")
'''
# run in the copy: the new cell untraced, then traced on the v5e extract
# with every other op of cca.hop (by name) named as an op of the new scope
DRIVE = '''
import contextlib, json, pathlib, sys, time
sys.path[:0] = [str(pathlib.Path.cwd()), sys.argv[1]]
from jax.profiler import ProfileData
from bench import harness, readings, stages

assert harness.BENCH == pathlib.Path.cwd() / "bench"
names = json.loads(pathlib.Path(sys.argv[3]).read_text())
hop = sorted(k for k, v in names.items() if "/cca.hop/" in v)[::2]
names = {k: (v.replace("/cca.hop/", "/cca.rhizome_bcast/") if k in hop
             else v, set()) for k, v in names.items()}
cell = harness.load_cell("tiny-uniform-bfs.inc100k")
untraced = harness.run_cell(cell, 3, 1.5, False, time.perf_counter())


@contextlib.contextmanager
def traced(box):
    box[0] = sys.argv[2]
    yield


harness.traced = traced
harness.read_trace = lambda d: ProfileData.from_text_proto(
    pathlib.Path(d).read_text())
stages.loop_op_names = lambda *a: names
keep = {}
out = harness.run_cell(cell, 3, 1.5, True, time.perf_counter(), keep=keep)
view = harness.RunView(cell=cell, window=keep["window"], setup_s=0.0,
                       trace=keep["trace"])
cycles = sum(r["result"].cycles for r in readings.traced_batches(view)
             ) * keep["trace"]["loop_seen_share"]
out["want"] = {s: keep["trace"]["stages"][s] / 1e3 / cycles
               for s in ("cca.rhizome_bcast", "cca.hop")}
out["bfs"] = [m["name"] for m in harness.load_cell(
    "sbm50k-bfs.inc100k").per_layer]
out["untraced"] = untraced
print(json.dumps(out, default=str))
'''


def digests(root: pathlib.Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).digest()
            for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_new_stream_kind_and_scope_metric_enter_as_new_files_only(
        tmp_path):
    extract = tmp_path / "first_batch.textproto"
    extract.write_text(first_batch_of_scoped_extract())
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", root)
    before = digests(root)
    old = json.loads((root / "BENCHMARK.json").read_text())

    # new files: the stream kind, a configuration of it, a reader
    (root / "bench/streams/uniform.py").write_text(NEW_KIND)
    config = json.loads((root / "bench/configs/sbm50k-bfs.json").read_text())
    config.update(
        name="tiny-uniform-bfs",
        graph=dict(kind="uniform", n_vertices=512, n_edges=4000,
                   increments=10, seed=2**31 + 5, weights="unit"),
        machine=dict(config["machine"], height=8, width=8, n_vertices=512))
    (root / "bench/configs/tiny-uniform-bfs.json").write_text(
        json.dumps(config))
    (root / "bench/metrics/rhizome_us_per_cycle.thru.py").write_text(
        NEW_READER)
    # new entries in BENCHMARK.json, none changed
    new = json.loads(json.dumps(old))
    new["configs"].append(dict(
        name="tiny-uniform-bfs", source="https://example.org/uniform",
        file="bench/configs/tiny-uniform-bfs.json", reduced=[],
        why="a throwaway deployment of a new stream kind"))
    new["workloads"].append(dict(
        name="tiny-uniform-bfs.inc100k", config="tiny-uniform-bfs",
        traffic="inc100k", chips=1, why="a throwaway cell"))
    new["per_layer"].append(dict(
        name="rhizome_us_per_cycle.thru", unit="us", better="lower",
        source="device_trace", layer="machine stage", moves="edges_per_s",
        workloads=["tiny-uniform-bfs.inc100k"]))
    (root / "BENCHMARK.json").write_text(json.dumps(new))

    proc = subprocess.run(
        [sys.executable, "-c", DRIVE, str(harness.ROOT / "src"),
         str(extract), str(SCOPED_NAMES)],
        cwd=root, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["untraced"]["correct"] and out["untraced"]["problems"] == []
    assert set(out["untraced"]["metrics"]) == {"edges_per_s", "setup_s"}
    # the new cell reads every per-layer metric the benchmark had, with
    # no entry changed, and its own; the old cells do not read the new one
    assert out["correct"] and out["problems"] == []
    old_layer = {m["name"] for m in old["per_layer"]}
    assert set(out["metrics"]) == old_layer | {"rhizome_us_per_cycle.thru"}
    assert set(out["bfs"]) == old_layer and len(old_layer) == 10
    for name, scope in (("rhizome_us_per_cycle.thru", "cca.rhizome_bcast"),
                        ("hop_us_per_cycle.thru", "cca.hop")):
        got = out["metrics"][name]["value"]
        assert got == pytest.approx(out["want"][scope]) and got > 0

    after = digests(root)
    assert {p: after[p] for p in before if p != "BENCHMARK.json"} == {
        p: d for p, d in before.items() if p != "BENCHMARK.json"}
    grown = json.loads((root / "BENCHMARK.json").read_text())
    for key, value in old.items():
        if isinstance(value, list):
            assert grown[key][:len(value)] == value
        else:
            assert grown[key] == value


def test_a_trace_that_dropped_the_tails_later_events_fails_the_run(
        chip_trace, monkeypatch):
    # the reduction finds a traced batch with no device loop in the trace
    reduce_trace = harness.reduce_trace
    monkeypatch.setattr(harness, "reduce_trace", lambda pd, names: dict(
        reduce_trace(pd, names), batches_unseen=1))
    out = harness.run_cell(tiny_cell(BFS), 7, 1.5, True, time.perf_counter())
    assert any("dropped the device's later events" in p
               for p in out["problems"]), out["problems"]


def test_a_trace_that_lost_the_ops_of_a_loop_fails_the_run(
        chip_trace, monkeypatch):
    # the whole extract: its ops stop within the first of its nine loops
    monkeypatch.setattr(harness, "read_trace",
                        lambda d: chip_extract(whole=True))
    out = harness.run_cell(tiny_cell(BFS), 7, 1.5, True, time.perf_counter())
    assert any("lost the ops of 8 runs of the device loop" in p
               for p in out["problems"]), out["problems"]
