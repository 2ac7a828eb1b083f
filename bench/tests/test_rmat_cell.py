"""The Graph500 R-MAT deployment (``rmat16-bfs``): its stream pinned byte
for byte and of the published shape, its cell resolved to four rhizome
roots, and the cell's run on the CPU at a tiny grid, with the rhizome
protocol's two readers, down to the comparison with the benchmark's own
reference; the same run with the timed path broken has to come out not
correct, and so does the control."""
from __future__ import annotations

import copy
import json
import pathlib
import time

import numpy as np
import pytest

from bench import control, harness, stream
from test_cells import altered_answer, half_batch
from test_stream import stream_sha256
from test_traced_view import chip_trace  # noqa: F401 (a fixture)

BENCH = pathlib.Path(stream.__file__).resolve().parent
CELL = "rmat16-bfs.inc100k"
READERS = ("rhizome_fwd_per_kedge.thru", "rhizome_links_per_kedge.thru")
# sha256 of the configuration's whole stream (every increment's shape,
# dtype and bytes, in order), as this deployment was first measured
STREAM_SHA256 = \
    "e5cb0382ff3e104d83b921d08222cbd5eea022924874d699b00b5586a5cc7521"
# the tiny deployment: Graph500's recipe at SCALE 9 (512 vertices, 8,192
# edges) on an 8x8 grid
TINY_GRAPH = dict(scale=9, n_vertices=512, n_edges=16 * 512)
TINY_MACHINE = dict(height=8, width=8, n_vertices=512)


def graph() -> dict:
    return json.loads((BENCH / "configs" / "rmat16-bfs.json").read_text())[
        "graph"]


@pytest.fixture(scope="module")
def whole():
    return stream.make_stream(graph())


def test_the_stream_is_pinned_byte_for_byte(whole):
    assert [len(x) for x in whole] == [104_858] * 6 + [104_857] * 4
    assert stream_sha256(whole) == STREAM_SHA256


def test_the_stream_has_graph500s_shape(whole):
    g = graph()
    e = np.concatenate(whole)
    assert len(e) == g["n_edges"] == g["edgefactor"] * 2 ** g["scale"]
    keys = (e[:, 0].astype(np.int64) << 32) | e[:, 1]
    assert len(np.unique(keys)) == len(e) and (e[:, 0] != e[:, 1]).all()
    assert e[:, :2].min() >= 0 and e[:, :2].max() < 2 ** g["scale"]
    deg = np.bincount(e[:, 0], minlength=g["n_vertices"])
    assert deg.max() > 256 * np.median(deg)


def test_another_seed_draws_another_stream():
    small = dict(graph(), **TINY_GRAPH)
    a, b = stream.make_stream(small), stream.make_stream(dict(small))
    c = stream.make_stream(dict(small, seed=2**31 + 77))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(np.concatenate(a), np.concatenate(c))
    with pytest.raises(ValueError, match="edgefactor"):
        stream.make_stream(dict(small, n_edges=1000))


def test_the_cell_resolves_four_roots_and_512_slots():
    cell = harness.load_cell(CELL)
    cfg = harness.engine_config(cell.config)
    assert (cfg.rhizome_cap, cfg.slots, cfg.ghost_slots) == (4, 512, 256)
    assert cfg.tail_insert
    assert (cfg.lanes, cfg.chan_cap, cfg.queue_cap) == (8, 64, 256)
    assert cfg.io_stream_cap == 6553
    assert {m["name"] for m in cell.per_layer} >= set(READERS)
    cfg.validate()


def tiny_cell() -> harness.Cell:
    cell = harness.load_cell(CELL)
    cell.config = copy.deepcopy(cell.config)
    cell.config["graph"].update(TINY_GRAPH)
    cell.config["machine"].update(TINY_MACHINE)
    return cell


def run(trace=False, keep=None):
    return harness.run_cell(tiny_cell(), 7, 1.5, trace, time.perf_counter(),
                            keep=keep)


def test_the_cell_runs_correct_at_a_tiny_grid():
    keep = {}
    out = run(keep=keep)
    assert out["correct"], out["compared"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["problems"] == []
    assert set(out["metrics"]) == {"edges_per_s", "setup_s"}
    done = [r["result"] for r in keep["window"]["batches"]
            if r["done"] is not None]
    assert sum(r.rfwds for r in done) > 0
    ctrl = control.control_readings(tiny_cell().config, keep)
    assert any(v > out["compared"][k]["limit"] for k, v in ctrl.items()), ctrl


def test_a_traced_run_reads_the_rhizome_counters():
    # the CPU trace has no TPU device plane: the trace readers find
    # nothing and are left out; the counter readers still read
    out = run(trace=True)
    assert out["correct"]
    assert set(out["metrics"]) == {"machine_cycles_per_kedge.thru", *READERS}
    fwd, links = (out["metrics"][m]["value"] for m in READERS)
    assert fwd > links > 0


def test_a_traced_run_reads_every_shared_metric_and_its_own(chip_trace):
    # fed the traced tail of a v5e extract in place of the CPU's trace,
    # the cell reads the ten per-layer metrics every cell reads, with no
    # edit, and the two of the rhizome protocol
    out = run(trace=True)
    assert out["correct"] and out["problems"] == []
    shared = {m["name"] for m in harness.load_cell(
        "sbm50k-bfs.inc100k").per_layer}
    assert len(shared) == 10
    assert set(out["metrics"]) == shared | set(READERS)
    assert all(m["value"] > 0 for m in out["metrics"].values())


@pytest.mark.parametrize("reader", READERS)
def test_a_reader_reads_nothing_where_the_engine_has_no_counter(reader):
    class Old:      # an IncrementResult from before the counters
        cycles = 10
    view = harness.RunView(cell=None, window=dict(batches=[dict(
        edges=100, done=1.0, result=Old())]), setup_s=0.0, trace=None)
    assert harness.load_reader(reader)(view) is None


def frozen_step(monkeypatch):
    """The device loop returns its state unchanged, with the record of a
    multi-root machine (reporting neither quiescence nor a wedge)."""
    from repro.core import engine

    def frozen(cfg, app, st, limit):
        return st, (0, False, 0, 0, 0, 0, 0, np.zeros(2, np.int32)), None
    monkeypatch.setattr(engine, "_increment_device_loop", frozen)


@pytest.mark.parametrize("fault", [frozen_step, half_batch, altered_answer])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    out = run()
    assert not out["correct"], out["compared"]
