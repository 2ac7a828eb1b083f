"""One run of one cell: set-up, the timed window, the read-back, the
comparison with the benchmark's own reference, and the metrics.

Everything that belongs to one configuration, traffic mix or metric is
found by name from ``BENCHMARK.json``:

- ``bench/configs/<config>.json``: the deployment (graph stream, machine,
  program preset, session kind, standing queries and their limits);
- ``bench/streams/<kind>.py``: the generator of the stream kind that the
  configuration's ``graph`` section names;
- ``bench/traffic/<traffic>.json``: the mix, read by the generator module
  ``bench/drivers/<driver>.py`` that the file names;
- ``bench/sessions/<session>.py``: how the configuration's queries are
  served;
- ``bench/checks/<app>.py``: the plain reference of each query's app and
  the number compared;
- ``bench/metrics/<metric>.py``: the reader of each metric; a per-layer
  reader of a traced run finds the device time of every ``cca.*`` scope
  of the engine's device loop by its name in ``RunView.trace``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def load_json(path: pathlib.Path):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    entry: dict        # the BENCHMARK.json workload entry
    config: dict       # bench/configs/<config>.json
    traffic: dict      # bench/traffic/<traffic>.json
    end_to_end: list   # BENCHMARK.json metrics this cell reports
    per_layer: list


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", "setup_s") in e2e_names


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"unknown workload {name!r}")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(name=name, entry=entry,
                config=load_json(root / cfg_entry["file"]),
                traffic=load_json(BENCH / "traffic"
                                  / f"{entry['traffic']}.json"),
                end_to_end=e2e, per_layer=layer)


def engine_config(config: dict):
    """The configuration's machine: the program preset it names, with the
    deployment's own fields (grid, vertices, edge storage, IO cells,
    chunk) set from the file."""
    p = config["preset"]
    presets = importlib.import_module(p["module"])
    spec = next(s for s in presets.cca_shapes() if s.name == p["shape"])
    cfg = getattr(presets, p["function"])(spec)
    return dataclasses.replace(cfg, **config["machine"])


def describe(cfg) -> str:
    keys = ("height", "width", "n_vertices", "edge_cap", "ghost_slots",
            "rhizome_cap", "io_cells", "io_stream_cap", "lanes", "chan_cap",
            "queue_cap", "futq_cap", "chunk", "backend", "max_cycles")
    return " ".join(f"{k}={getattr(cfg, k)}" for k in keys)


def start_jax(chips: int) -> dict:
    """Keep JAX's persistent compilation cache at a fixed path in the
    checkout, unless ``JAX_COMPILATION_CACHE_DIR`` names one, with every
    program in it; then :func:`device_info`."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return device_info(chips)


def device_info(chips: int) -> dict:
    """The accelerator as JAX reports it; exits nonzero where JAX finds no
    accelerator or fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform not in ("tpu", "gpu"):
        raise SystemExit(f"bench: no accelerator (platform {d.platform!r});"
                         " the benchmark has no CPU fallback")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell asks for {chips} chips, JAX "
                         f"sees {len(devs)}")
    return dict(platform=d.platform, kind=d.device_kind, count=len(devs))


def memory_peak_bytes() -> int | None:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts the XLA programs built (compiled, or loaded from the
    persistent cache) and the cache's hits and misses, as JAX's
    monitoring events report them."""

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = self.hits = self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event.endswith("backend_compile_duration"):
            self.compiles += 1

    def _event(self, event, **kw):
        if event.endswith("cache_hits"):
            self.hits += 1
        elif event.endswith("cache_misses"):
            self.misses += 1

    def snapshot(self) -> tuple[int, int, int]:
        return self.compiles, self.hits, self.misses


@dataclasses.dataclass
class RunView:
    """What a metric reader reads."""
    cell: Cell
    window: dict             # drivers' window record
    setup_s: float
    trace: dict | None       # reduce_trace() of the traced window


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(metrics: list, view: RunView) -> dict:
    out = {}
    for m in metrics:
        v = load_reader(m["name"])(view)
        if v is not None:
            out[m["name"]] = dict(value=float(v), unit=m["unit"])
    return out


# seconds of whole batches at the window's end that a traced run
# profiles: the device trace holds every op of every machine cycle, some
# 1.2M device events a second of the engine's loop on a v5e, and keeps
# only its first ~7.3M (the first ~5.9 s of busy device), so a longer
# tail would read low
TRACE_SECONDS = 4.0
# seconds between starting the profiler and the first traced batch: the
# device's tracer starts collecting some time after start_trace returns
# (once on a v5e it missed the first ~0.1 s of a loop that began 0.12 s
# after), and an op it misses would under-read the tail
TRACE_SETTLE_S = 2.0


@contextlib.contextmanager
def traced(box: list):
    """Profile the enclosed block, under the host span ``bench.traced``,
    into a temporary directory, which ``box[0]`` names after the block:
    :func:`read_trace` reads it once the window has closed."""
    import jax
    d = tempfile.mkdtemp(prefix="bench-trace-")
    box[0] = d
    jax.profiler.start_trace(d)
    try:
        time.sleep(TRACE_SETTLE_S)
        with span("bench.traced"):
            yield
    finally:
        jax.profiler.stop_trace()


def read_trace(d: str):
    """The ProfileData of the trace that :func:`traced` wrote to ``d``
    (``None`` where it wrote none), and ``d`` removed."""
    from jax.profiler import ProfileData
    try:
        pbs = sorted(pathlib.Path(d).rglob("*.xplane.pb"))
        return ProfileData.from_file(str(pbs[-1])) if pbs else None
    finally:
        shutil.rmtree(d, ignore_errors=True)


def reduce_trace(pd, op_names: dict) -> dict | None:
    """The traced view that readers get: :func:`bench.trace.reduce`'s
    numbers with :func:`bench.stages.reduce`'s (device time by every
    ``cca.*`` scope, ``repro.*`` span time, idle by span) where the
    device loop ran in the tail; ``None`` where the trace does not
    reduce."""
    from bench import stages, trace as trace_mod
    t = time.perf_counter()
    out = trace_mod.reduce(pd)
    t_old = time.perf_counter() - t
    if out is not None:
        out.update(stages.reduce(pd, op_names=op_names) or {})
    log(f"bench.trace.reduce {t_old:.3f}s, bench.stages.reduce "
        f"{time.perf_counter() - t - t_old:.3f}s")
    return out


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def ingested_edges(preload: list, window: dict, batches: list) -> np.ndarray:
    """Every edge of the set-up's batches and of the window's done ones."""
    done = list(preload) + [b for b, r in zip(batches, window["batches"])
                            if r["done"] is not None]
    return (np.concatenate(done) if done
            else np.zeros((0, 3), np.int32))


def resolve_queries(queries: list, incs: list, n: int) -> list:
    """Each query with its ``source``: the vertex of out-degree rank
    ``hub`` over the whole stream (0 = the busiest; ties to the lower id),
    so that every standing query starts where the stream has edges."""
    deg = np.bincount(np.concatenate(incs)[:, 0], minlength=n)
    order = np.argsort(-deg, kind="stable")
    return [dict(q, source=int(order[q["hub"]])) for q in queries]


def compared_name(query: dict, chk) -> str:
    return f"{query['app']}_hub{query['hub']}_{chk.NAME}"


def compare(config: dict, queries: list, got: dict,
            edges: np.ndarray) -> dict:
    """``{name: {value, limit}}`` for every standing query."""
    n = config["graph"]["n_vertices"]
    out = {}
    for q, query in enumerate(queries):
        chk = importlib.import_module(f"bench.checks.{query['app']}")
        want = chk.reference(n, edges, query["source"])
        out[compared_name(query, chk)] = dict(
            value=chk.compare(got[q], want), limit=query["limit"])
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, device: dict | None = None,
             keep: dict | None = None) -> dict:
    """One run; returns the result line's object, with ``problems``: what
    makes the run unfit to report (programs built inside the window, a
    trace that does not reduce, a declared metric that reads nothing).
    ``keep``, where given, receives the read-back answers, the set-up's
    and the window's batches (for the control readings)."""
    import jax

    from bench import stages, stream, trace as trace_mod
    clock = time.perf_counter
    counter = CompileCounter()
    config, traffic = cell.config, cell.traffic

    t = clock()
    incs = stream.make_stream(config["graph"])
    log(f"stream: {sum(len(e) for e in incs)} edges in {len(incs)} "
        f"increments, the deployment's, the same for seed {seed} as for "
        f"any ({clock() - t:.3f}s)")
    cfg = engine_config(config)
    log(f"config {cell.entry['config']}: preset "
        f"{config['preset']['function']}({config['preset']['shape']}) "
        f"resolved {describe(cfg)}")
    sessions = importlib.import_module(f"bench.sessions.{config['session']}")
    driver = importlib.import_module(f"bench.drivers.{traffic['driver']}")
    queries = resolve_queries(config["queries"], incs,
                              config["graph"]["n_vertices"])
    log("sources: " + ", ".join(f"{q['app']} hub {q['hub']} = vertex "
                                f"{q['source']}" for q in queries))
    sess = sessions.open(cfg, queries)
    t = clock()
    sess.warm()
    jax.block_until_ready(sess.eng.state)
    log(f"warm-up: {clock() - t:.3f}s (programs built, cache hits, "
        f"misses: {counter.snapshot()})")
    preload, batches = driver.plan(incs, traffic)
    if preload:
        t = clock()
        for b in preload:
            sess.run(b)
        jax.block_until_ready(sess.eng.state)
        log(f"preload: {len(preload)} batches, "
            f"{sum(len(b) for b in preload)} edges ({clock() - t:.3f}s)")
    before = counter.snapshot()
    setup_s = clock() - t_start

    box = [None]
    tail = (TRACE_SECONDS, lambda: traced(box)) if trace else None
    window = driver.drive(sess.run, batches, seconds, clock, span, tail)
    after = counter.snapshot()
    in_window = (after[0] - before[0]) + (after[1] - before[1])
    problems = []
    if in_window:
        problems.append(f"{in_window} programs compiled or loaded from the "
                        f"cache inside the window")
    recs = window["batches"]
    done = [r for r in recs if r["done"] is not None]
    failed = sum(r["failed"] for r in recs)
    log(f"window: {len(recs)} batches, {len(done)} done, {failed} failed, "
        f"{sum(r['edges'] for r in done)} edges, "
        f"{sum(r['result'].cycles for r in done)} machine cycles, "
        f"{window['end'] - window['t0']:.3f}s"
        + (f", error {window['error'][:400]}" if window["error"] else ""))
    log("window batches [start s, wall s, machine cycles]: " + json.dumps(
        [[round(r["start"] - window["t0"], 4),
          round(r["done"] - r["start"], 4), r["result"].cycles]
         for r in done]) + f"; traced from batch {window['tail_from']} to "
        f"{window['tail_to']}")

    with span("bench.readback"):
        got = {q: np.asarray(sess.values(q))
               for q in range(len(queries))}
    peak = memory_peak_bytes()
    # the device loop's arguments as shapes, for its HLO text below
    loop = stages.loop_args(sess.eng) if box[0] is not None else None
    sess.close()
    del sess
    gc.collect()
    reduced = op_names = None
    if box[0] is not None:
        t = clock()
        pd = read_trace(box[0])
        t_read = clock() - t
        op_names = stages.loop_op_names(*loop)
        t_hlo = clock() - t - t_read
        reduced = reduce_trace(pd, op_names) if pd is not None else None
        del pd
        log(f"trace read in {t_read:.3f}s; the device loop's HLO op names "
            f"in {t_hlo:.3f}s (programs built, cache hits, misses: "
            f"{counter.snapshot()}); trace reduced in "
            f"{clock() - t - t_read - t_hlo:.3f}s: "
            + (json.dumps({k: v for k, v in reduced.items()
                           if k.endswith(("_ns", "_share", "_cut",
                                          "_unseen")) or k.startswith("n_")})
               if reduced else "no device programs in the traced tail"))

    if trace and reduced is None:
        problems.append("the trace of the window's tail could not be reduced")
    elif trace and reduced["batches_unseen"]:
        problems.append(f"the trace holds no device loop in "
                        f"{reduced['batches_unseen']} of the traced batches: "
                        f"it dropped the device's later events, and the tail "
                        f"would read low")
    elif trace and reduced["loops_cut"]:
        problems.append(f"the trace lost the ops of {reduced['loops_cut']} "
                        f"runs of the device loop in the tail (their outer "
                        f"while missing or short): it began collecting late, "
                        f"and the tail would read low")
    view = RunView(cell=cell, window=window, setup_s=setup_s, trace=reduced)
    declared = cell.per_layer if trace else cell.end_to_end
    metrics = read_metrics(declared, view)
    missing = [m["name"] for m in declared if m["name"] not in metrics]
    if missing:
        problems.append(f"metrics that read nothing: {missing}")
    t = clock()
    edges = ingested_edges(preload, window, batches)
    compared = compare(config, queries, got, edges)
    log(f"reference over {len(edges)} edges: {clock() - t:.3f}s")
    if keep is not None:
        keep.update(got=got, window=window, preload=preload,
                    batches=batches, edges=edges, queries=queries,
                    trace=reduced, op_names=op_names)
    correct = failed == 0 and all(c["value"] <= c["limit"]
                                  for c in compared.values())
    dev = dict(device or dict(platform=jax.devices()[0].platform,
                              kind=jax.devices()[0].device_kind,
                              count=len(jax.devices())))
    dev["memory_peak_bytes"] = peak
    out = dict(correct=correct, attempted=len(recs), failed=failed,
               metrics=metrics, device=dev, problems=problems)
    if trace and reduced is not None:
        dev["busy_s"] = reduced["busy_ns"] / 1e9
        dev["window_s"] = reduced["window_ns"] / 1e9
        out["breakdown"] = dict(
            device_ops=reduced["device_ops"], idle_gaps=reduced["idle_gaps"],
            idle_by_span=reduced.get("idle_by_span", [])[:trace_mod.TOP])
    out["compared"] = compared
    return out
