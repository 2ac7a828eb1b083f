"""Engine-backend throughput benchmark -> ``results/bench_engine.json``.

Starts the perf trajectory for the cycle engine itself (DESIGN §6):

  * per-backend (jnp lax chunk runners vs the fused Pallas cycle
    megakernel, interpret mode off-TPU) cycles/sec and end-to-end
    increment wall-clock on a BFS stream, with a bit-exactness check
    between the two backends;
  * a livelock-detector smoke on both backends (undersized buffers must
    raise, DESIGN §4.2) — CI fails on either regression;
  * the ``--only increments`` ci-scale wall-clock trajectory: the
    pre-PR chunked host driver baseline vs the sync-free
    ``collect_traces=False`` fast path (recorded via ``--record-increments``,
    not in the CI smoke job — it is minutes of CPU).

Scales are engine-local (like SKEW_SCALES): the megakernel's VMEM
residency claim is about the chip state, so a small grid measures the
same effect in seconds.
"""
from __future__ import annotations

import json
import pathlib
import time

import numpy as np

from repro.core import EngineConfig, StreamingEngine
from repro.core.reference import bfs_levels
from repro.graph.streams import StreamSpec, make_stream

OUT = "results/bench_engine.json"

ENGINE_SCALES = {
    "ci": dict(height=8, width=8, n_vertices=256, n_edges=2048, chunk=64),
    "mid": dict(height=16, width=16, n_vertices=2048, n_edges=16_384,
                chunk=128),
}


def _cfg(p: dict, backend: str, **kw) -> EngineConfig:
    base = dict(height=p["height"], width=p["width"],
                n_vertices=p["n_vertices"], edge_cap=8,
                ghost_slots=max(64, 4 * p["n_edges"]
                                // (8 * p["height"] * p["width"])),
                queue_cap=64, chan_cap=16, futq_cap=8,
                io_stream_cap=2 ** 18, chunk=p["chunk"], backend=backend)
    base.update(kw)
    return EngineConfig(**base)


def bench_engine(scale: str = "ci", profile: bool = False) -> dict:
    """Backend throughput + parity + livelock smoke; merges into OUT.

    ``profile=True`` additionally runs both backends with
    ``telemetry=True`` on the same stream (the ``--profile`` flag of
    ``benchmarks.run``): records the telemetry overhead vs the plain
    run, asserts a non-empty frame log, and dumps the congestion heatmap
    under ``results/profile/`` (DESIGN §8).
    """
    p = ENGINE_SCALES.get(scale, ENGINE_SCALES["mid"])  # paper -> mid grid
    spec = StreamSpec(n_vertices=p["n_vertices"], n_edges=p["n_edges"],
                      increments=2, sampling="edge", seed=3)
    incs = make_stream(spec)
    want = bfs_levels(p["n_vertices"], np.concatenate(incs), 0)
    n_cells = p["height"] * p["width"]

    rec: dict = dict(scale=scale, grid=f'{p["height"]}x{p["width"]}',
                     n_vertices=p["n_vertices"], n_edges=p["n_edges"],
                     chunk=p["chunk"], backends={})
    finals = {}
    for backend in ("jnp", "pallas"):
        eng = StreamingEngine(_cfg(p, backend), "bfs")
        eng.seed(0, 0.0)
        eng.run_increment(incs[0], max_cycles=2_000_000)  # warm the jit
        t0 = time.time()
        r = eng.run_increment(incs[1], max_cycles=2_000_000)
        dt = time.time() - t0
        np.testing.assert_array_equal(eng.values(p["n_vertices"]), want)
        finals[backend] = eng.state
        rec["backends"][backend] = dict(
            cycles=r.cycles, wall_s=round(dt, 3),
            cyc_per_s=round(r.cycles / dt, 1),
            cell_cycles_per_s=round(r.cycles / dt * n_cells, 0),
            execs=r.execs, hops=r.hops, total_cycles=eng.total_cycles)
        if profile:
            rec["backends"][backend]["profile"] = _profile_backend(
                p, backend, incs, dt, r)

    # bit-exactness across backends (the CI parity gate)
    for name, a, b in zip(finals["jnp"]._fields, finals["jnp"],
                          finals["pallas"]):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"state leaf '{name}' diverged between backends")
    rec["parity"] = "bit-exact"

    # livelock detector must fire identically on both backends
    rec["livelock_detector"] = {}
    bad = make_stream(StreamSpec(n_vertices=64, n_edges=400, increments=1,
                                 seed=21))[0]
    for backend in ("jnp", "pallas"):
        cfg = EngineConfig(height=8, width=8, n_vertices=64, edge_cap=2,
                           ghost_slots=48, queue_cap=8, chan_cap=2,
                           futq_cap=2, io_stream_cap=2048, chunk=64,
                           backend=backend)
        eng = StreamingEngine(cfg, "bfs")
        eng.seed(0, 0.0)
        try:
            eng.run_increment(bad, max_cycles=200_000)
            raise AssertionError(
                f"livelock NOT detected on backend={backend}")
        except RuntimeError as e:
            assert "livelock" in str(e), e
            rec["livelock_detector"][backend] = "fires"
    if profile:
        # recovery-path cost on the happy path: checkpoint-cadence sweep
        # + faults-off vs faults-on wall-clock deltas (DESIGN §9)
        from benchmarks.resilience_smoke import profile_resilience
        rec["resilience_profile"] = profile_resilience(scale)
    _merge(rec, key=f"engine_{scale}")
    return rec


def _profile_backend(p: dict, backend: str, incs, plain_wall_s: float,
                     plain_result) -> dict:
    """Telemetry-on rerun of the timed increment: overhead, frame-total
    reconciliation against the plain run, and the heatmap dump."""
    from repro.obs import engine_rates, write_heatmap

    eng = StreamingEngine(_cfg(p, backend, telemetry=True), "bfs")
    eng.seed(0, 0.0)
    eng.run_increment(incs[0], max_cycles=2_000_000)  # warm the jit
    t0 = time.time()
    r = eng.run_increment(incs[1], max_cycles=2_000_000)
    dt = time.time() - t0
    assert r.frames is not None and len(r.frames) > 0, \
        f"telemetry produced no frames on backend={backend}"
    # the final frame must reconcile exactly with the scalar counters of
    # the bit-exact plain run (DESIGN §8)
    t = r.frames.totals()
    assert (t["hops"], t["execs"]) == (plain_result.hops,
                                       plain_result.execs), \
        (f"frame totals diverged from counters on backend={backend}: "
         f"{t} vs hops={plain_result.hops} execs={plain_result.execs}")
    heat = write_heatmap(f"results/profile/heatmap_{backend}.json",
                         eng.cfg, r.frames)
    return dict(
        wall_s=round(dt, 3),
        overhead_pct=round(100 * (dt - plain_wall_s) / plain_wall_s, 1),
        frames=len(r.frames), dropped=r.frames.dropped,
        rates={k: round(v, 3) if isinstance(v, float) else v
               for k, v in engine_rates(r.frames).items()},
        heatmap=heat)


def record_increments_wallclock(scale: str = "ci") -> dict:
    """End-to-end ``--only increments`` wall-clock with the sync-free
    fast path, stored next to the recorded pre-PR baseline (minutes of
    CPU — run locally, not in the CI smoke job)."""
    from benchmarks import paper_experiments as pe
    rec = {}
    for sampling in ("edge", "snowball"):
        _, wall = pe.bench_cycles_per_increment(scale, sampling)
        rec[f"{sampling}_wall_s"] = round(wall, 1)
    data = _merge({f"fast_path_{scale}": rec}, key="increments_wallclock")
    base = data.get("increments_wallclock", {}).get(f"pre_pr_baseline_{scale}")
    if base:
        rec["speedup_vs_pre_pr"] = {
            k: round(base[k] / rec[k], 2) for k in rec if k in base}
        _merge({f"fast_path_{scale}": rec}, key="increments_wallclock")
    return rec


def _merge(rec: dict, key: str) -> dict:
    p = pathlib.Path(OUT)
    p.parent.mkdir(parents=True, exist_ok=True)
    data = json.loads(p.read_text()) if p.exists() else {}
    if key == "increments_wallclock":
        data.setdefault(key, {}).update(rec)
    else:
        data[key] = rec
    p.write_text(json.dumps(data, indent=1))
    return data


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="ci", choices=list(ENGINE_SCALES))
    ap.add_argument("--record-increments", action="store_true")
    args = ap.parse_args()
    print(json.dumps(bench_engine(args.scale), indent=1))
    if args.record_increments:
        print(json.dumps(record_increments_wallclock(args.scale), indent=1))
