"""Benchmark harness: one entry per paper table/figure + kernel micros
+ the roofline table.  Prints ``name,value,derived`` CSV lines.

  PYTHONPATH=src python -m benchmarks.run [--scale ci|mid|paper] [--only X]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time


def _csv(name, *fields):
    print(",".join([name] + [str(f) for f in fields]), flush=True)


def bench_paper(scale: str, only=None) -> None:
    from benchmarks import paper_experiments as pe

    if only in (None, "increments"):
        for sampling in ("edge", "snowball"):
            rows, wall = pe.bench_cycles_per_increment(scale, sampling)
            for r in rows:
                _csv(f"fig8_9/{sampling}", f'inc{r["increment"]}',
                     f'edges={r["edges"]}',
                     f'ingest_cycles={r["ingest_cycles"]}',
                     f'ingest_bfs_cycles={r["ingest_bfs_cycles"]}')
    if only in (None, "energy"):
        for r in pe.bench_energy(scale):
            _csv("table2", r["sampling"], r["mode"],
                 f'energy_uj={r["energy_uj"]}', f'time_us={r["time_us"]}')
    if only in (None, "allocator"):
        for r in pe.bench_allocator(scale):
            _csv("fig5_allocator", r["allocator"],
                 f'cycles={r["cycles"]}', f'hops={r["hops"]}',
                 f'ghosts={r["ghosts"]}',
                 f'mean_ghost_hops={r["mean_ghost_hops"]}',
                 f'max_ghost_hops={r["max_ghost_hops"]}')
    if only in (None, "activation"):
        act = pe.bench_activation(scale, "edge",
                                  out_npz="results/activation_edge.npz")
        for mode, s in act.items():
            _csv("fig6_7_activation", mode, f'cycles={s["cycles"]}',
                 f'mean_active={s["mean_active"]}',
                 f'peak={s["peak_active"]}',
                 f'util_pct={s["mean_util_pct"]}')
    if only in (None, "skew"):
        for r in pe.bench_skew(scale):
            _csv("skew_rhizome", f'rhizome_cap={r["rhizome_cap"]}',
                 f'cycles={r["cycles"]}', f'hops={r["hops"]}',
                 f'stalls={r["stalls"]}',
                 f'max_degree={r["max_degree"]}',
                 f'deg_over_edge_cap={r["degree_over_edge_cap"]}',
                 f'rhizomes={r["rhizomes"]}',
                 f'multi_root={r["multi_root_vertices"]}',
                 f'max_fanout={r["max_fanout"]}',
                 f'ghosts={r["ghosts"]}')
    if only in (None, "skew", "lanes"):
        # virtual lanes on the same R-MAT stream at the PRE-oversize
        # queue_cap (results/bench_lanes.json; the CI lanes-smoke gate:
        # lanes>=2 must complete where lanes=1 livelocks, DESIGN §7)
        rows, base = pe.bench_lanes(scale)
        for r in rows:
            _csv("lanes_hub", f'lanes={r["lanes"]}',
                 f'queue_cap={r["queue_cap"]}', r["status"],
                 f'cycles={r["cycles"]}', f'stalls={r["stalls"]}')
        _csv("lanes_hub", "lanes=1", f'queue_cap={base["queue_cap"]}',
             f'{base["status"]} (oversize baseline)',
             f'cycles={base["cycles"]}', f'stalls={base["stalls"]}')
    if only in (None, "throughput"):
        t = pe.bench_engine_throughput(scale)
        _csv("engine_throughput", f'cycles={t["cycles"]}',
             f'wall_s={t["wall_s"]}',
             f'cell_cycles_per_s={t["cell_cycles_per_s"]}')


def bench_engine_backends(scale: str, profile: bool = False) -> None:
    """jnp vs pallas cycle-megakernel backends: throughput, bit-exact
    parity gate, livelock-detector smoke (results/bench_engine.json).
    ``--profile`` adds the telemetry-on runs: overhead, frame counts and
    the heatmap dumps under ``results/profile/`` (DESIGN §8)."""
    from benchmarks.engine_throughput import bench_engine
    r = bench_engine(scale, profile=profile)
    for backend, b in r["backends"].items():
        _csv("engine_backend", backend, f'cycles={b["cycles"]}',
             f'wall_s={b["wall_s"]}',
             f'cell_cycles_per_s={b["cell_cycles_per_s"]}')
        if "profile" in b:
            pr = b["profile"]
            _csv("engine_profile", backend,
                 f'overhead_pct={pr["overhead_pct"]}',
                 f'frames={pr["frames"]}',
                 f'execs_per_cycle={pr["rates"]["execs_per_cycle"]}',
                 f'hops_per_cycle={pr["rates"]["hops_per_cycle"]}',
                 f'trace={pr["trace"]}', f'heatmap={pr["heatmap"]}')
    _csv("engine_backend", "parity", r["parity"])
    for backend, v in r["livelock_detector"].items():
        _csv("engine_backend", f"livelock_{backend}", v)
    if "resilience_profile" in r:
        pr = r["resilience_profile"]
        for k in ("ckpt_every_1", "ckpt_every_2", "faults_zero_rate",
                  "faults_live"):
            _csv("resilience_profile", k, f'wall_s={pr[k]["wall_s"]}',
                 f'overhead_pct={pr[k]["overhead_pct"]}')


def bench_faults(scale: str, profile: bool = False) -> None:
    """Resilience gates (DESIGN §9): seeded fault stream converging
    exact via repair, kill-and-resume bit-exactness, livelock recovery
    via escalation — both backends (results/bench_engine.json)."""
    from benchmarks.resilience_smoke import bench_resilience
    r = bench_resilience(scale, profile=profile)
    for backend, b in r["fault_smoke"].items():
        _csv("fault_smoke", backend, b["status"], f'cycles={b["cycles"]}',
             f'dropped={b["dropped"]}', f'duplicated={b["duplicated"]}',
             f'corrupted={b["corrupted"]}',
             f'blackout_hits={b["blackout_hits"]}')
    for backend, b in r["kill_resume"].items():
        _csv("kill_resume", backend, b["status"],
             f'resumed_at={b["resumed_at"]}')
    rc = r["recovery"]
    _csv("livelock_recovery", rc["status"],
         f'escalated_lanes={rc["escalated_lanes"]}',
         f'attempts={rc["attempts"]}', f'wedge_cycle={rc["wedge_cycle"]}')
    if profile:
        pr = r["profile"]
        for k in ("ckpt_every_1", "ckpt_every_2", "faults_zero_rate",
                  "faults_live"):
            _csv("resilience_profile", k, f'wall_s={pr[k]["wall_s"]}',
                 f'overhead_pct={pr[k]["overhead_pct"]}')


def bench_serve(scale: str) -> None:
    """Multi-tenant query serving (repro.mq, DESIGN §10): Q=8 mixed
    BFS/SSSP/CC/widest batch over a live R-MAT stream vs Q serial runs
    (results/bench_serve.json).  Fails loudly if any tenant's values
    diverge from its single-query run or the aggregate speedup falls
    under 2x — the CI serve-smoke gate."""
    from benchmarks.serve_bench import bench_serve as run_serve
    r = run_serve(scale)
    for qrec in r["queries"]:
        _csv("serve_query", f'slot={qrec["slot"]}', qrec["app"],
             f'source={qrec["source"]}',
             f'serial_cycles={qrec["serial_cycles"]}',
             "exact" if qrec["exact"] else "MISMATCH")
    _csv("serve_batch", f'qbatch={r["qbatch"]}',
         f'batch_cycles={r["batch_cycles"]}',
         f'serial_total={r["serial_cycles_total"]}',
         f'speedup={r["speedup"]}',
         f'p50={r["p50_cycles"]}', f'p99={r["p99_cycles"]}',
         f'deferrals={r["deferrals"]}')
    if not r["all_exact"]:
        raise SystemExit("bench_serve: per-query values diverged from "
                         "the single-query runs")
    if r["speedup"] < 2.0:
        raise SystemExit(f'bench_serve: aggregate speedup {r["speedup"]} '
                         "< 2x over serial runs")


def bench_dist(scale: str) -> None:
    """Sharded-CCA chunk throughput at 1/2/4/8 fake host devices."""
    from benchmarks.dist_scaling import run_scaling
    failed = []
    for r in run_scaling(scale):
        if "error" in r:
            failed.append(r["devices"])
            _csv("dist_scaling", f'devices={r["devices"]}', "FAILED",
                 r["error"][:120].replace("\n", " "))
            continue
        _csv("dist_scaling", f'devices={r["devices"]}', f'grid={r["grid"]}',
             f'cell_cycles_per_s={r["cell_cycles_per_s"]}',
             f'wall_s={r["wall_s"]}', f'compile_s={r["compile_s"]}')
    if failed:  # fail loudly so the CI dist-smoke job goes red
        raise SystemExit(f"bench_dist failed at device counts {failed}")


def bench_kernels() -> None:
    import jax
    import numpy as np
    from repro.kernels.embedding_bag.ops import embedding_bag
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.spmm.ops import spmm_sorted_coo

    def timeit(f, *a, n=3, **kw):
        f(*a, **kw)  # compile
        t0 = time.time()
        for _ in range(n):
            jax.block_until_ready(f(*a, **kw))
        return (time.time() - t0) / n * 1e6

    k = jax.random.PRNGKey(0)
    q = jax.random.normal(k, (1, 256, 4, 64))
    kk = jax.random.normal(k, (1, 256, 2, 64))
    us = timeit(flash_attention, q, kk, kk, interpret=True)
    _csv("kernel/flash_attention", f"{us:.0f}us",
         "interpret-mode (CPU); deploy target TPU")
    rng = np.random.default_rng(0)
    x = rng.standard_normal((512, 64), dtype=np.float32)
    src = rng.integers(0, 512, 4096).astype(np.int32)
    dst = np.sort(rng.integers(0, 512, 4096).astype(np.int32))
    us = timeit(spmm_sorted_coo, x, src, dst, 512, interpret=True)
    _csv("kernel/spmm_onehot_mxu", f"{us:.0f}us", "interpret-mode")
    tbl = rng.standard_normal((4096, 64), dtype=np.float32)
    idx = rng.integers(0, 4096, (64, 4)).astype(np.int32)
    us = timeit(embedding_bag, tbl, idx, interpret=True)
    _csv("kernel/embedding_bag", f"{us:.0f}us", "interpret-mode")


def bench_roofline(path="results/dryrun.json") -> None:
    p = pathlib.Path(path)
    if not p.exists():
        _csv("roofline", "SKIPPED", f"{path} missing - run dryrun first")
        return
    data = json.loads(p.read_text())
    for key, r in sorted(data.items()):
        if not r.get("ok"):
            _csv("roofline", key, "FAILED", r.get("error", "")[:80])
            continue
        rf = r.get("roofline", {})
        _csv("roofline", key,
             f't_comp={rf.get("t_compute", 0):.4f}s',
             f't_mem={rf.get("t_memory", 0):.4f}s',
             f't_coll={rf.get("t_collective", 0):.4f}s',
             f'dominant={rf.get("dominant")}',
             f'frac={rf.get("roofline_fraction", 0):.3f}')


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="ci",
                    choices=["ci", "mid", "paper"])
    ap.add_argument("--only", default=None,
                    help="increments|energy|allocator|activation|skew|"
                         "lanes|throughput|engine|faults|dist|serve|"
                         "kernels|roofline")
    ap.add_argument("--profile", action="store_true",
                    help="telemetry-on engine runs (overhead + "
                         "congestion heatmap under "
                         "results/profile/) and the resilience cost "
                         "profile (checkpoint cadence + fault deltas)")
    args = ap.parse_args()
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    pathlib.Path("results").mkdir(exist_ok=True)
    print("benchmark,fields...", flush=True)
    try:
        if args.only in (None, "kernels"):
            bench_kernels()
        if args.only in (None, "roofline"):
            bench_roofline()
        if args.only in (None, "engine"):
            bench_engine_backends(args.scale, profile=args.profile)
        if args.only in (None, "faults"):
            bench_faults(args.scale, profile=args.profile)
        if args.only in (None, "dist"):
            bench_dist(args.scale)
        if args.only in (None, "serve"):
            bench_serve(args.scale)
        if args.only is None or args.only not in ("kernels", "roofline",
                                                  "engine", "faults",
                                                  "dist", "serve"):
            bench_paper(args.scale, args.only)
    except Exception as e:
        # a LivelockError message carries the flight-recorder wedge
        # report — print it whole so the CI log shows WHERE the machine
        # wedged, and exit nonzero so the job goes red (DESIGN §9)
        from repro.core.engine import LivelockError
        if isinstance(e, LivelockError):
            print(f"\nLIVELOCK (cycle {e.cycle}, chunk {e.chunk}):\n{e}",
                  file=sys.stderr, flush=True)
            raise SystemExit(3)
        raise


if __name__ == "__main__":
    main()
