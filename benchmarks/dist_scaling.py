"""bench_dist: chunk throughput of the GSPMD-sharded CCA engine vs fake
CPU host-device count.  The XLA fake-device count is locked at first jax
init, so each device count runs in its own CPU-only subprocess (worker
mode below); the parent collects ``results/bench_dist.json``.  These are
CPU numbers.  On a TPU host it refuses to run: one process holds the
chip, and the sharded path on real chips is ``chip_smoke.py --chips 4``.

  PYTHONPATH=src JAX_PLATFORMS=cpu python -m benchmarks.run --scale ci --only dist
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import time

# scale -> (height, width, n_vertices, stream_edges, chunk, timed_chunks)
SCALES = {
    "ci": (8, 8, 64, 160, 32, 6),
    "mid": (16, 16, 1024, 4096, 64, 8),
    "paper": (32, 32, 50_000, 102_000, 128, 8),
}


def worker(scale: str, devices: int) -> dict:
    """Runs inside a subprocess whose XLA_FLAGS pin the device count."""
    import jax
    import numpy as np
    from jax.sharding import AxisType
    from repro.core.apps import BFS
    from repro.core.config import EngineConfig
    from repro.core.engine import StreamingEngine, run_chunk_body
    from repro.core.ingest import load_stream
    from repro.dist.sharding import cca_state_shardings

    H, W, V, E, chunk, timed = SCALES[scale]
    cfg = EngineConfig(height=H, width=W, n_vertices=V,
                       ghost_slots=max(16, 4 * V // (H * W)),
                       io_stream_cap=max(256, 2 * E // W), chunk=chunk)
    rng = np.random.default_rng(0)
    one = np.float32(1.0).view(np.int32)
    edges = np.stack([rng.integers(0, V, E), rng.integers(0, V, E),
                      np.full(E, one)], 1).astype(np.int32)
    eng = StreamingEngine(cfg, "bfs")
    eng.seed(0, 0.0)
    cfg = eng.cfg
    st, _ = load_stream(cfg, eng.state, edges)

    mesh = jax.make_mesh((devices, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    shards = cca_state_shardings(mesh, jax.eval_shape(lambda: st))
    st = jax.device_put(st, shards)
    step = jax.jit(lambda s: run_chunk_body(cfg, BFS, s),
                   in_shardings=(shards,), out_shardings=shards)
    t0 = time.time()
    st = jax.block_until_ready(step(st))          # compile + warm
    compile_s = time.time() - t0
    t0 = time.time()
    for _ in range(timed):
        st = step(st)
    jax.block_until_ready(st)
    wall = time.time() - t0
    cycles = timed * cfg.chunk
    return dict(devices=devices, grid=f"{H}x{W}", chunk=cfg.chunk,
                timed_chunks=timed, compile_s=round(compile_s, 2),
                wall_s=round(wall, 4),
                cell_cycles_per_s=round(H * W * cycles / wall, 1))


def run_scaling(scale: str, device_counts=(1, 2, 4, 8),
                out_path: str = "results/bench_dist.json") -> list[dict]:
    import jax
    if jax.default_backend() != "cpu":
        raise SystemExit(
            f"bench_dist runs fake CPU host devices in subprocesses and "
            f"refuses a {jax.default_backend()} host, whose chip this "
            "process holds; run the sharded path on the chips in one "
            "process with `python chip_smoke.py --chips 4`")
    rows = []
    for d in device_counts:
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={d}"
        env["JAX_PLATFORMS"] = "cpu"
        env.setdefault("PYTHONPATH", "src")
        try:
            r = subprocess.run(
                [sys.executable, "-m", "benchmarks.dist_scaling",
                 "--worker", "--scale", scale, "--devices", str(d)],
                env=env, capture_output=True, text=True, timeout=1800)
        except subprocess.TimeoutExpired:
            rows.append(dict(devices=d, error="worker timeout (1800s)"))
            continue
        if r.returncode != 0:
            rows.append(dict(devices=d, error=r.stderr[-500:]))
            continue
        rows.append(json.loads(r.stdout.splitlines()[-1]))
    p = pathlib.Path(out_path)
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(json.dumps(dict(scale=scale, rows=rows), indent=1))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="ci", choices=sorted(SCALES))
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--worker", action="store_true")
    args = ap.parse_args()
    if args.worker:
        print(json.dumps(worker(args.scale, args.devices)), flush=True)
    else:
        for row in run_scaling(args.scale):
            print(row)


if __name__ == "__main__":
    main()
