"""bench_serve — multi-tenant query serving over a live R-MAT stream.

One MQSession carries a mixed BFS / SSSP / CC / widest batch of Q
queries through an evolving R-MAT graph (repro.mq, DESIGN §10), with
FrontDesk admission and per-query time-to-quiescence accounting.  The
baseline is the same stream replayed once per query on a single-query
engine; both sides are measured in MACHINE CYCLES (the architectural
metric every other bench uses), so

    speedup = sum(serial cycles over Q queries) / batched cycles

is the aggregate-throughput multiplier of sharing one diffusion wave —
global quiescence of the batch tracks the *slowest* tenant, not the sum,
so Q-way batches land well above 1x (the serve-smoke CI gate pins >= 2x
for the Q=8 mix).

Per-query correctness is asserted against the single-query runs
(bit-exact — over-propagated neutral payloads no-op under monotone
relaxation), and the result record lands in ``results/bench_serve.json``.
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

from repro.core.alloc import rhizome_rcs
from repro.core.config import EngineConfig
from repro.core.engine import StreamingEngine
from repro.core.state import vals_index
from repro.graph.streams import StreamSpec, hashed_pair_weights, make_stream
from repro.mq.frontdesk import FrontDesk
from repro.mq.session import DEFAULT_SEEDS, MQSession
from repro.obs import metrics

SCALES = {
    # the serving mesh is sized ABOVE the single-query sweet spot on
    # purpose: one wave keeps an 8x8 grid's execute bandwidth busy by
    # itself (measured speedup there caps at ~1.9x), while a Q-way batch
    # exists to soak up idle cells — 16x16 gives it the headroom the
    # paper's machines have, and the same mix lands >2x
    "ci": dict(height=16, width=16, n_vertices=256, n_edges=700,
               increments=3, chunk=128),
    "mid": dict(height=24, width=24, n_vertices=1024, n_edges=4000,
                increments=5, chunk=256),
    "paper": dict(height=32, width=32, n_vertices=4096, n_edges=20000,
                  increments=10, chunk=256),
}

# the Q=8 mixed tenant batch (app, source); CC is the label-flood tenant
# and must be admitted before the stream starts
QUERY_MIX = (("bfs", 0), ("bfs", 17), ("bfs", 42), ("sssp", 5),
             ("sssp", 23), ("sssp", 77), ("cc", 0), ("widest", 11))


def _serve_cfg(p):
    return EngineConfig(
        height=p["height"], width=p["width"], n_vertices=p["n_vertices"],
        edge_cap=8,
        ghost_slots=max(64, 8 * p["n_edges"]
                        // (p["height"] * p["width"])),
        # a Q-way batch pushes ~Q machines' worth of relaxation waves
        # through one machine's buffers (same total message count as the
        # serial runs, compressed in time), so the serving preset scales
        # every congestion defence the single-query benches run with:
        # deep virtual lanes for the hub-convergent R-MAT traffic
        # (DESIGN §7), multi-root rhizomes so hub inserts shard over
        # co-equal roots (§4.5), 4x queue/channel depth for the
        # Q-amplified wave volume, and the tm_hiw ingest guard (§9) so
        # edge admission backs off instead of parking the fabric solid.
        # Undersized single-query margins (queue_cap=32, chan_cap=16,
        # lanes<=4) wedge on this stream — measured, not theoretical.
        queue_cap=256, chan_cap=64, futq_cap=8, io_stream_cap=8192,
        lanes=8, rhizome_cap=4, telemetry=True, ingest_guard=True,
        chunk=p["chunk"], max_cycles=4_000_000)


def _stream(p):
    """Symmetric R-MAT increments with per-edge random weights in
    (0.1, 1.0] — undirected for the CC tenant, weighted so the SSSP and
    widest tenants diverge from BFS."""
    spec = StreamSpec(n_vertices=p["n_vertices"], n_edges=p["n_edges"],
                      increments=p["increments"], kind="rmat",
                      symmetric=True, seed=7)
    return hashed_pair_weights(make_stream(spec))


def _serial_run(cfg, app, source, incs):
    """Single-query baseline: same stream, one tenant, total cycles."""
    eng = StreamingEngine(cfg, app)
    if app == "cc":
        vids = np.arange(cfg.n_vertices, dtype=np.int64)[None, :]
        ks = np.arange(cfg.rhizome_cap, dtype=np.int64)[:, None]
        r, c, s = rhizome_rcs(eng.cfg, vids, ks)
        labels = np.broadcast_to(vids.astype(np.float32), r.shape)
        at = vals_index(eng.cfg, r, c, s)
        eng.state = eng.state._replace(vals=eng.state.vals.at[at].set(labels))
    else:
        eng.seed(source, DEFAULT_SEEDS[app])
    cycles = 0
    for e in incs:
        cycles += eng.run_increment(e).cycles
    return eng, cycles


def bench_serve(scale: str = "ci",
                out_json: str = "results/bench_serve.json") -> dict:
    p = SCALES[scale]
    cfg = _serve_cfg(p)
    incs = _stream(p)
    Q = len(QUERY_MIX)

    # ---- batched serving run ----
    ses = MQSession(cfg, qbatch=Q, apps=[a for a, _ in QUERY_MIX])
    fd = FrontDesk(ses)
    for app, src in QUERY_MIX:
        if app == "cc":
            ses.admit(app, src)       # label flood: pre-stream only
        else:
            fd.submit(app, src)
    batch_cycles = 0
    for e in incs:
        batch_cycles += fd.step(e).cycles
    # one empty flush beat so tenants that last changed in the final
    # increment observe a quiet boundary and settle (counted — it is
    # machine time the serving run spent)
    batch_cycles += fd.step(np.zeros((0, 3), np.int32)).cycles
    for q, s in enumerate(ses.slots):
        if s.state != "free":
            fd.receipts.append(ses.retire(q))
    # a retired slot's value plane stays intact until the slot is
    # recycled (nothing was re-admitted) — read per-query results now.
    # Tenants land in slots in ADMISSION order (CC grabs the first free
    # slot, FrontDesk fills the rest in submit order), so map each
    # (app, source) tenant to its slot via the retirement receipts.
    batch_values = {q: ses.values(q) for q in range(Q)}
    slot_of = {(r["app"], r["source"]): r["slot"] for r in fd.receipts}

    # ---- serial baselines + per-query exactness gate ----
    serial_cycles = []
    exact = []
    for q, (app, src) in enumerate(QUERY_MIX):
        eng, cyc = _serial_run(cfg, app, src, incs)
        serial_cycles.append(cyc)
        exact.append(bool(np.array_equal(
            eng.values(), batch_values[slot_of[(app, src)]])))

    lat = [r["latency_cycles"] for r in fd.receipts
           if r["latency_cycles"] is not None]
    summary = metrics.summarize(lat, unit="cycles")
    speedup = float(sum(serial_cycles)) / max(1, batch_cycles)
    rec = dict(
        scale=scale, qbatch=Q,
        queries=[dict(slot=slot_of[(a, s)], app=a, source=s,
                      serial_cycles=serial_cycles[q],
                      exact=exact[q])
                 for q, (a, s) in enumerate(QUERY_MIX)],
        receipts=[{k: v for k, v in r.items() if k != "values"}
                  for r in fd.receipts],
        latency=summary,
        p50_cycles=summary.get("p50"), p99_cycles=summary.get("p99"),
        batch_cycles=int(batch_cycles),
        serial_cycles_total=int(sum(serial_cycles)),
        speedup=round(speedup, 3),
        all_exact=all(exact),
        deferrals=fd.deferrals,
    )
    pathlib.Path(out_json).parent.mkdir(exist_ok=True)
    pathlib.Path(out_json).write_text(json.dumps(rec, indent=2))
    return rec
