"""Bring-up smoke run of the streaming engine on a TPU.

  python chip_smoke.py             # one chip: engine phase + serving phase
  python chip_smoke.py --chips 4   # four chips: the sharded path only

Engine phase: the paper's deployment -- the GraphChallenge-style SBM
stream of 50K vertices and 1M edges in 10 edge-sampled increments --
streamed through ``StreamingEngine`` at ``chip_32x32_50k`` state size on
the ``jnp`` backend with virtual lanes (DESIGN §7), the BFS levels
checked exactly against NetworkX after every increment.  Serving phase:
a Q=4 bfs/sssp/widest ``MQSession`` batch over the first two increments
with hashed pair weights, each run in IO passes of at most 4,096 edges
(``serve_config_for``), every tenant checked against its oracle.
``--chips 4``: ``run_chunk_body`` under ``cca_state_shardings`` on a
(2,2) mesh, compared leaf by leaf with the same chunks on one chip.

Every line before the last is a bring-up record, not a benchmark.  The
last line is one JSON object naming the device, printed only when every
phase passed.  With no TPU, or without the repo's ``src/`` beside it,
the script exits nonzero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import re
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
SRC = ROOT / "src"

N_VERTICES = 50_000
N_EDGES = 1_000_000
INCREMENTS = 10
MIN_INCREMENTS = 3
# wall-clock budget of the engine phase: past MIN_INCREMENTS, the next
# increment starts only if the last one would still fit
ENGINE_BUDGET_S = 540.0
SERVE_INCREMENTS = 2
SERVE_MIX = (("bfs", 0), ("sssp", 3), ("widest", 5), ("bfs", 7))
# sharded phase: the first increment is loaded, then run in chunks; the
# chunk count is the one-chip run's count to quiescence
SHARDED_MAX_CHUNKS = 400


def say(*a) -> None:
    print(*a, flush=True)


def device_check(chips: int) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    say(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (platform {d.platform!r}); "
                         "this run has no CPU fallback")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} but JAX sees "
                         f"{len(devs)} device(s)")
    return dict(platform=d.platform, kind=d.device_kind, count=len(devs))


def paper_spec():
    from repro.configs.cca_paper import cca_shapes
    return next(s for s in cca_shapes() if s.name == "chip_32x32_50k")


def paper_stream():
    from repro.graph.streams import StreamSpec, make_stream
    t = time.perf_counter()
    incs = make_stream(StreamSpec(n_vertices=N_VERTICES, n_edges=N_EDGES,
                                  increments=INCREMENTS, sampling="edge"))
    say(f"stream: sbm/edge {N_VERTICES} vertices "
        f"{sum(len(e) for e in incs)} edges in {len(incs)} increments "
        f"(host generation {time.perf_counter() - t:.3f}s)")
    return incs


def _compile_device_loop(eng) -> float:
    """Compile the engine's device loop for the engine's current state
    ahead of the first increment, so compile time is reported apart; the
    jitted call then reuses this executable."""
    from repro.core.engine import _increment_device_loop
    t = time.perf_counter()
    _increment_device_loop.lower(eng.cfg, eng.app, eng.state,
                                 eng.cfg.max_cycles).compile()
    return time.perf_counter() - t


def engine_phase(cfg, incs) -> None:
    """Stream ``incs`` through one BFS engine; exact NetworkX check after
    every increment."""
    import jax
    import numpy as np
    from repro.core.engine import StreamingEngine
    from repro.core.reference import bfs_levels

    eng = StreamingEngine(cfg, "bfs")
    eng.seed(0, 0.0)
    n = eng.cfg.n_vertices
    say(f"engine phase: grid {cfg.height}x{cfg.width} n_vertices={n} "
        f"slots/cell={eng.cfg.slots} lanes={cfg.lanes} "
        f"chan_cap={cfg.chan_cap} queue_cap={cfg.queue_cap} "
        f"backend={cfg.backend} chunk={cfg.chunk}")
    say(f"engine phase: device-loop compile {_compile_device_loop(eng):.3f}s")
    seen = []
    t_phase = time.perf_counter()
    last = 0.0
    for i, e in enumerate(incs):
        spent = time.perf_counter() - t_phase
        if i >= MIN_INCREMENTS and spent + last > ENGINE_BUDGET_S:
            say(f"engine phase: stopping after {i} increments "
                f"({spent:.1f}s spent, budget {ENGINE_BUDGET_S:.0f}s)")
            break
        t = time.perf_counter()
        r = eng.run_increment(e)
        jax.block_until_ready(eng.state)
        wall = time.perf_counter() - t
        seen.append(e)
        t = time.perf_counter()
        got = eng.values(n)
        want = bfs_levels(n, np.concatenate(seen), 0)
        ref_s = time.perf_counter() - t
        exact = bool(np.array_equal(got, want))
        say(f"bring-up increment {i}: edges={len(e)} cycles={r.cycles} "
            f"hops={r.hops} execs={r.execs} stalls={r.stalls} "
            f"allocs={r.allocs} wall_s={wall:.3f} "
            f"reached={int((got < 1e9).sum())} "
            f"{'exact' if exact else 'MISMATCH'} (reference {ref_s:.1f}s)")
        if not exact:
            bad = np.nonzero(got != want)[0]
            raise SystemExit(f"engine phase: BFS levels differ from NetworkX "
                             f"at {len(bad)} vertices, first {bad[:8]}")
        last = time.perf_counter() - t_phase - spent
    ghosts = eng.vertex_object_stats()["ghosts"]
    say(f"engine phase: {len(seen)} increments exact, ghost nodes "
        f"{ghosts} of {eng.cfg.n_cells * eng.cfg.ghost_slots}")


def serve_phase(cfg, incs) -> None:
    """Q-batched tenants over the first increments; every slot against
    its oracle (bfs exact, sssp/widest at the test_mq tolerances)."""
    import jax
    import numpy as np
    from repro.core.reference import bfs_levels, sssp_dists, widest_caps
    from repro.graph.streams import hashed_pair_weights
    from repro.mq.session import MQSession

    incs = hashed_pair_weights(incs)
    ses = MQSession(cfg, qbatch=len(SERVE_MIX),
                    apps=[a for a, _ in SERVE_MIX])
    for q, (app, src) in enumerate(SERVE_MIX):
        ses.admit(app, src, slot=q)
    say(f"serve phase: Q={len(SERVE_MIX)} mix {SERVE_MIX} msg_words="
        f"{ses.eng.cfg.msg_words} io_stream_cap={cfg.io_stream_cap}")
    say(f"serve phase: device-loop compile "
        f"{_compile_device_loop(ses.eng):.3f}s")
    for i, e in enumerate(incs):
        t = time.perf_counter()
        r = ses.run_increment(e)
        jax.block_until_ready(ses.eng.state)
        wall = time.perf_counter() - t
        say(f"bring-up serve increment {i}: edges={len(e)} "
            f"cycles={r.cycles} hops={r.hops} execs={r.execs} "
            f"stalls={r.stalls} wall_s={wall:.3f}")
    edges = np.concatenate(incs)
    n = cfg.n_vertices
    w = edges[:, 2].view(np.float32)
    for q, (app, src) in enumerate(SERVE_MIX):
        got = ses.values(q)
        if app == "bfs":
            ok = np.array_equal(got, bfs_levels(n, edges, src))
        elif app == "sssp":
            ok = np.allclose(got, sssp_dists(n, edges, w, src),
                             rtol=1e-5, atol=0)
        else:
            ok = np.allclose(got, widest_caps(n, edges, src),
                             rtol=1e-6, atol=0)
        say(f"serve phase: slot {q} {app}@{src} "
            f"{'matches its oracle' if ok else 'MISMATCH'}")
        if not ok:
            raise SystemExit(f"serve phase: slot {q} ({app}@{src}) "
                             "differs from its oracle")


def sharded_phase(cfg, edges) -> None:
    """``run_chunk_body`` under ``cca_state_shardings`` on a (2,2) mesh,
    bit-exact per state leaf with the same chunks on one chip."""
    import jax
    import numpy as np
    from jax.sharding import AxisType, PartitionSpec as P
    from repro.core.apps import BFS
    from repro.core.engine import StreamingEngine, quiescent, run_chunk_body
    from repro.core.ingest import load_stream
    from repro.core.reference import bfs_levels
    from repro.dist.sharding import cca_state_shardings

    eng = StreamingEngine(cfg, "bfs")
    eng.seed(0, 0.0)
    cfg = eng.cfg
    st0, spill = load_stream(cfg, eng.state, edges)
    if len(spill):
        raise SystemExit("sharded phase: edges overflow the IO streams")
    st0 = jax.device_get(st0)

    one = jax.devices()[0]
    f1 = jax.jit(lambda s: run_chunk_body(cfg, BFS, s))
    sA = jax.device_put(st0, one)
    t = time.perf_counter()
    f1 = f1.lower(sA).compile()
    say(f"sharded phase: one-chip chunk compile "
        f"{time.perf_counter() - t:.3f}s")
    t = time.perf_counter()
    chunks = 0
    while chunks < SHARDED_MAX_CHUNKS:
        sA, chunks = f1(sA), chunks + 1
        if bool(quiescent(sA)):
            break
    jax.block_until_ready(sA)
    wall1 = time.perf_counter() - t
    if not bool(quiescent(sA)):
        raise SystemExit(f"sharded phase: one-chip run did not quiesce in "
                         f"{SHARDED_MAX_CHUNKS} chunks")
    say(f"bring-up one-chip run: {chunks} chunks x {cfg.chunk} cycles "
        f"wall_s={wall1:.3f} device={sA.vals.sharding.device_set}")

    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=jax.devices()[:4])
    shards = cca_state_shardings(mesh, jax.eval_shape(lambda: st0))
    if shards.vals.spec != P("data", "model", None, None):
        raise SystemExit(f"sharded phase: vals spec {shards.vals.spec}")
    sB = jax.device_put(st0, shards)
    per_dev = {s.device.id: s.data.shape for s in sB.vals.addressable_shards}
    say(f"sharded phase: vals {sB.vals.shape} shards per device {per_dev}")
    if len(per_dev) != 4 or any(v[:2] != (cfg.height // 2, cfg.width // 2)
                                for v in per_dev.values()):
        raise SystemExit("sharded phase: state is not tiled over 4 chips")
    t = time.perf_counter()
    f4 = jax.jit(lambda s: run_chunk_body(cfg, BFS, s),
                 in_shardings=(shards,), out_shardings=shards
                 ).lower(sB).compile()
    permutes = re.findall(r"collective-permute(?:-start)?\(", f4.as_text())
    say(f"sharded phase: 4-chip chunk compile {time.perf_counter() - t:.3f}s "
        f"collective-permutes={len(permutes)}")
    t = time.perf_counter()
    for _ in range(chunks):
        sB = f4(sB)
    jax.block_until_ready(sB)
    say(f"bring-up 4-chip run: {chunks} chunks x {cfg.chunk} cycles "
        f"wall_s={time.perf_counter() - t:.3f} "
        f"devices={len(sB.vals.sharding.device_set)}")

    diverged = [name for name, a, b in zip(sA._fields, sA, sB)
                if not np.array_equal(np.asarray(a), np.asarray(b))]
    say(f"sharded phase: {len(sA._fields) - len(diverged)}/"
        f"{len(sA._fields)} state leaves bit-exact")
    if diverged:
        raise SystemExit(f"sharded phase: leaves diverged: {diverged}")
    eng.state = sB
    got = eng.values(cfg.n_vertices)
    if not np.array_equal(got, bfs_levels(cfg.n_vertices, edges, 0)):
        raise SystemExit("sharded phase: BFS levels differ from NetworkX")
    say(f"sharded phase: BFS levels exact vs NetworkX "
        f"(reached {int((got < 1e9).sum())})")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = run only the sharded (2,2)-mesh phase")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"chip_smoke: the repo's src/repro is not beside "
                         f"this script ({SRC})")
    sys.path.insert(0, str(SRC))
    from repro.compile_cache import use_compile_cache
    say(f"compile cache: {use_compile_cache()}")
    device = device_check(args.chips)
    from repro.configs.cca_paper import serve_config_for, stream_config_for
    spec = paper_spec()
    incs = paper_stream()
    t = time.perf_counter()
    if args.chips == 4:
        sharded_phase(stream_config_for(spec), incs[0])
    else:
        engine_phase(stream_config_for(spec), incs)
        serve_phase(serve_config_for(spec), incs[:SERVE_INCREMENTS])
    say(f"phases done in {time.perf_counter() - t:.1f}s")
    print(json.dumps(dict(ok=True, device=device)), flush=True)


if __name__ == "__main__":
    main()
