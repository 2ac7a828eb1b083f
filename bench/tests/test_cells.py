"""Each cell's run on the CPU at a tiny grid, called as functions (the
harness's look for a chip is skipped), down to the comparison with the
benchmark's own reference; and the same runs with the timed path broken
underneath, which have to come out not correct."""
from __future__ import annotations

import copy
import time

import numpy as np
import pytest

from bench import harness

CELLS = ("sbm50k-bfs.inc100k", "sbm50k-mq4.inc4k")
# the tiny deployment: an 8x8 grid (8 IO cells) holding a 512-vertex,
# 10,000-edge stream of the same shape; batches shrink with the IO width
TINY_GRAPH = dict(n_vertices=512, n_edges=10_000)
TINY_MACHINE = dict(height=8, width=8, n_vertices=512)
TINY_TRAFFIC = {"inc4k": dict(batch_edges=512)}


def tiny_cell(name: str) -> harness.Cell:
    cell = harness.load_cell(name)
    cell.config = copy.deepcopy(cell.config)
    cell.config["graph"].update(TINY_GRAPH)
    cell.config["machine"].update(TINY_MACHINE)
    cell.traffic = dict(cell.traffic,
                        **TINY_TRAFFIC.get(cell.entry["traffic"], {}))
    return cell


def run(name: str, seed: int = 7, seconds: float = 1.5, trace=False):
    return harness.run_cell(tiny_cell(name), seed, seconds, trace,
                            time.perf_counter())


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_correct_at_a_tiny_grid(name):
    out = run(name)
    assert out["correct"], out["compared"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "compared" and out["problems"] == []
    cell = harness.load_cell(name)
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in out["metrics"].values())


def test_traced_run_reports_the_counter_metrics_on_cpu():
    # the CPU trace has no TPU device plane: the trace readers find
    # nothing and are left out; the counter reader still reads
    out = run("sbm50k-bfs.inc100k", trace=True)
    assert out["correct"]
    assert set(out["metrics"]) == {"machine_cycles_per_kedge.thru"}


def frozen_step(monkeypatch):
    """The device loop returns its state unchanged (reporting neither
    quiescence nor a wedge, so the engine's host loop does not spin on the IO
    residue)."""
    from repro.core import engine

    def frozen(cfg, app, st, limit):
        return st, (0, False, 0, 0, 0, 0, 0), None
    monkeypatch.setattr(engine, "_increment_device_loop", frozen)


def half_batch(monkeypatch):
    """Ingest leaves out the second half of every batch."""
    from repro.core import engine
    load = engine.load_stream

    def half(cfg, st, edges, limit=None):
        return load(cfg, st, edges[: len(edges) // 2], limit=limit)
    monkeypatch.setattr(engine, "load_stream", half)


def altered_answer(monkeypatch):
    """The read-back changes the value of one vertex that holds another
    value than the unreached mark (the source, where nothing else is
    reached)."""
    from repro.core import engine
    values = engine.StreamingEngine.values

    def altered(self, *a, **kw):
        v = np.array(values(self, *a, **kw))
        marks, counts = np.unique(v, return_counts=True)
        i = int(np.flatnonzero(v != marks[np.argmax(counts)])[0])
        v[i] = -(v[i] + 1.0)
        return v
    monkeypatch.setattr(engine.StreamingEngine, "values", altered)


@pytest.mark.parametrize("fault", [frozen_step, half_batch, altered_answer])
@pytest.mark.parametrize("name", CELLS)
def test_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    out = run(name)
    assert not out["correct"], out["compared"]
