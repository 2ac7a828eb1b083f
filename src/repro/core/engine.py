"""The cycle engine: composes routing, execution and ingestion into one
pure ``state -> state`` step, runs it to quiescence, and exposes the
streaming-increment API used by the experiments.

Cycle order (all fixed-shape, fully vectorized over the cell grid):

  1. hop_stage      channel heads advance one link (YX DOR, backpressure)
  2. staging        active actions stage one ``propagate`` message
  3. phase0         idle cells pop one action and run its compute step
  4. io_stage       IO cells inject the next streamed edge

Quiescence (the paper's Terminator object): no queued actions, no channel
occupancy, no active action, no deferred future tasks, no pending IO.
On a real pod this is a tree all-reduce of the pending counters; here it is
literally ``jnp.sum`` inside the jitted step — GSPMD lowers it to
``all-reduce`` when the grid is sharded (see the dry-run HLO).

Two execution backends share ``cycle_body`` (DESIGN §6):

  * ``backend="jnp"`` — lax chunk runners over the HBM-resident state;
  * ``backend="pallas"`` — the fused cycle megakernel
    (``kernels/cca_cycle``): K cycles per launch with the state leaves
    held in VMEM.  It runs in Pallas interpret mode off-TPU only: on a
    TPU backend the engine refuses it (:data:`PALLAS_ON_TPU`).

The streaming driver's default fast path (``collect_traces=False``) runs
the whole chunk loop of an increment — including the livelock detector —
as one device-side ``lax.while_loop`` per spill pass: exactly one jit
call and one scalar readback per pass.  Per-cycle activity traces are
opt-in (``collect_traces=True``) and use the chunked host loop.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.alloc import rhizome_rcs
from repro.core.apps import APPS, DiffusionApp
from repro.core.config import EngineConfig
from repro.core.exec_stage import phase0_stage, staging_stage
from repro.core.ingest import io_stage, load_stream
from repro.core.routing import hop_stage, park_stage
from repro.core.state import (RZ_FWD, RZ_LINK, TM_HOP, TM_HW_AQ, TM_L_OCC,
                              MachineState, init_state, root_addr,
                              self_cell_grid, vals_index)
from repro.obs import frames as obs_frames
from repro.obs import spans as obs_spans


class CycleStats(NamedTuple):
    active: jax.Array      # cells doing compute/staging work this cycle
    in_flight: jax.Array   # messages sitting in channels
    backlog: jax.Array     # queued actions
    hops: jax.Array        # link traversals this cycle
    quiescent: jax.Array   # bool


def _rc(cfg: EngineConfig):
    rows = jnp.arange(cfg.height, dtype=jnp.int32)[:, None]
    cols = jnp.arange(cfg.width, dtype=jnp.int32)[None, :]
    return (jnp.broadcast_to(rows, (cfg.height, cfg.width)),
            jnp.broadcast_to(cols, (cfg.height, cfg.width)))


def quiescent(st: MachineState) -> jax.Array:
    with jax.named_scope("cca.quiescent"):
        return ((jnp.sum(st.aq_n) == 0) & (jnp.sum(st.ch_n) == 0)
                & (jnp.sum(st.pk_n) == 0)
                & ~jnp.any(st.cvalid) & (jnp.sum(st.fq_n) == 0)
                & ~jnp.any(st.fwd_pending)
                & (jnp.sum(st.io_n - st.io_pos) == 0))


def cycle_body(cfg: EngineConfig, app: DiffusionApp, st: MachineState):
    """One machine cycle, no stats reductions: hop -> staging -> phase0 ->
    io.  The single copy of the cycle semantics, shared verbatim by the
    jnp chunk runners below and the Pallas cycle megakernel
    (``kernels/cca_cycle``).  Returns the per-cell activity masks as aux
    so ``cycle_step`` can build :class:`CycleStats` without recompute
    (callers that ignore them pay nothing — XLA DCEs the masks).

    Each stage runs under a ``jax.named_scope`` (``cca.hop``,
    ``cca.park``, ``cca.staging``, ``cca.phase0``, ``cca.io``,
    ``cca.telemetry``): the scope lands in the ``op_name`` metadata of
    its ops, so a profiler trace attributes device time per stage
    (DESIGN §8)."""
    rows, cols = _rc(cfg)
    busy0 = st.cvalid
    with jax.named_scope("cca.hop"):
        if cfg.telemetry:
            # per-lane occupancy integral at cycle entry (avg depth =
            # TM_L_OCC / cycles); the other planes accumulate inside the
            # stages where the grant/stall masks live (DESIGN §8)
            st = st._replace(
                tm_lane=st.tm_lane.at[..., TM_L_OCC].add(st.ch_n))
        st, hops = hop_stage(cfg, st, rows, cols)
    if cfg.lanes > 1:
        # re-inject parked transit messages right after the hop stage,
        # while freshly-vacated lane slots are still free (DESIGN §7);
        # with lanes == 1 nothing ever parks — skip for a bit-exact trace
        with jax.named_scope("cca.park"):
            st = park_stage(cfg, st, rows, cols)
    with jax.named_scope("cca.staging"):
        st, active_a = staging_stage(cfg, app, st, rows, cols)
    with jax.named_scope("cca.phase0"):
        st, popped = phase0_stage(cfg, app, st, rows, cols, busy0)
    with jax.named_scope("cca.io"):
        st = io_stage(cfg, st, rows, cols)
    if cfg.telemetry:
        with jax.named_scope("cca.telemetry"):
            hw = jnp.stack([st.aq_n, st.pk_n], axis=-1)
            st = st._replace(tm_hiw=jnp.maximum(st.tm_hiw, hw))
    st = st._replace(cycle=st.cycle + 1,
                     stat_hops=st.stat_hops + hops)
    return st, (active_a, popped, hops)


def cycle_step(cfg: EngineConfig, app: DiffusionApp, st: MachineState):
    st, (active_a, popped, hops) = cycle_body(cfg, app, st)
    stats = CycleStats(
        active=jnp.sum((active_a | popped).astype(jnp.int32)),
        in_flight=jnp.sum(st.ch_n) + jnp.sum(st.pk_n),
        backlog=jnp.sum(st.aq_n),
        hops=hops, quiescent=quiescent(st))
    return st, stats


def run_chunk_body(cfg: EngineConfig, app: DiffusionApp, st: MachineState):
    """Un-jitted fixed-length chunk (dry-run / roofline entry point: the
    caller jits this with the production-mesh shardings)."""
    def body(s, _):
        s2, _ = cycle_body(cfg, app, s)
        return s2, None
    st, _ = jax.lax.scan(body, st, None, length=cfg.chunk)
    return st


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=2)
def run_chunk(cfg: EngineConfig, app: DiffusionApp, st: MachineState):
    """Scan `cfg.chunk` cycles; freeze once quiescent (identity cycles).

    The stacked ``stats.quiescent`` records quiescence at cycle ENTRY
    (i.e. flags the frozen identity cycles), so ``argmax`` over it is
    exactly the number of cycles executed this chunk — in agreement with
    the state's own ``cycle`` counter and the sync-free device loop.
    """
    def body(s, _):
        done = quiescent(s)
        s2, stats = cycle_step(cfg, app, s)
        s = jax.tree.map(lambda a, b: jnp.where(done, a, b), s, s2)
        return s, stats._replace(quiescent=done)
    return jax.lax.scan(body, st, None, length=cfg.chunk)


def run_to_quiescence_while(cfg: EngineConfig, app: DiffusionApp,
                            st: MachineState, max_cycles=None):
    """Pure lax.while_loop runner (no traces) — the dry-run/roofline path."""
    mc = jnp.int32(max_cycles or cfg.max_cycles)
    start = st.cycle

    def cond(s):
        return (~quiescent(s)) & (s.cycle - start < mc)

    def body(s):
        s2, _ = cycle_body(cfg, app, s)
        return s2

    return jax.lax.while_loop(cond, body, st)


# Livelock detection granularity: this many consecutive chunks with zero
# executed actions while work is pending => message-dependent deadlock
# (DESIGN §4.2).  Shared by the device-side fast path and the host-side
# trace path so both backends fail identically.
LIVELOCK_CHUNKS = 8


def _livelock_msg(cfg: EngineConfig) -> str:
    return ("engine livelock: no action executed and no message hopped "
            f"for {LIVELOCK_CHUNKS * cfg.chunk} cycles with work pending "
            "— every virtual lane is stuck. "
            f"Enable virtual lanes (lanes>=2, currently {cfg.lanes}) so "
            "protocol traffic escapes head-of-line blocking, and/or "
            "increase chan_cap (>=4) / queue_cap "
            f"(>= aq_reserve+sys_reserve+8 = "
            f"{cfg.aq_reserve + cfg.sys_reserve + 8}) — see "
            "DESIGN.md §4.2/§7 buffer-sizing rules.")


class LivelockError(RuntimeError):
    """Message-dependent deadlock detected (DESIGN §4.2).

    Structured replacement for the bare ``RuntimeError`` string: carries
    the machine ``cycle`` at detection, the ``chunk`` index within the
    increment, and — when ``cfg.telemetry`` is on — the flight-recorder
    ``frames`` (:class:`repro.obs.FrameLog`; ``None`` otherwise).
    Subclasses ``RuntimeError`` with "livelock" in the message, so
    pre-existing ``except RuntimeError`` + substring handlers keep
    working without regex-parsing the message.
    """

    def __init__(self, msg: str, *, cycle: int, chunk: int, frames=None):
        super().__init__(msg)
        self.cycle = cycle
        self.chunk = chunk
        self.frames = frames


def _raise_livelock(cfg: EngineConfig, *, cycle: int, chunk: int,
                    frames=None):
    """Build and raise :class:`LivelockError`, appending the flight
    recorder's wedge report when frames were captured."""
    msg = _livelock_msg(cfg)
    if frames is not None and len(frames) >= 2:
        from repro.obs.flight import render_wedge_report
        msg = msg + "\n" + render_wedge_report(cfg, frames)
    raise LivelockError(msg, cycle=cycle, chunk=chunk, frames=frames)


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=2)
def _increment_device_loop(cfg: EngineConfig, app: DiffusionApp,
                           st: MachineState, limit):
    """One increment pass entirely on device: a ``lax.while_loop`` over
    chunks with the livelock detector folded in as a no-progress counter.

    Host<->device traffic per pass is exactly one donated state in and a
    handful of scalars out — no per-chunk ``int(stat_exec)`` syncs, no
    per-cycle stats transfer (the record ends with the rhizome counters,
    ``stat_rhz``, where ``cfg.rhizome_cap > 1``).  Each chunk either runs
    :func:`run_to_quiescence_while` capped at ``cfg.chunk`` cycles
    (backend="jnp") or one fused Pallas megakernel launch of
    ``cfg.chunk`` cycles (backend="pallas"); both leave the state frozen
    at the exact quiescence cycle, so the two backends are bit-exact.
    """
    start = st.cycle

    if cfg.backend == "pallas":
        from repro.kernels.cca_cycle.ops import cca_cycle_chunk

        def chunk(s):
            return cca_cycle_chunk(cfg, app, s)[0]
    else:
        def chunk(s):
            return run_to_quiescence_while(cfg, app, s,
                                           max_cycles=cfg.chunk)

    def cond(carry):
        s, _, noprog, _ = carry
        return ((~quiescent(s)) & (s.cycle - start < limit)
                & (noprog < LIVELOCK_CHUNKS))

    def body(carry):
        s, last_prog, noprog, ring = carry
        s = chunk(s)
        with jax.named_scope("cca.chunk"):
            # progress = an action completed OR a message hopped a link:
            # with virtual lanes a chunk may be all-transit (messages
            # draining through sibling lanes while a hub lane is full), so
            # exec-only progress would false-positive; no-progress now
            # means every lane AND every cell is stuck (DESIGN §7)
            prog = s.stat_exec + s.stat_hops
            noprog = jnp.where(prog == last_prog, noprog + 1, jnp.int32(0))
            if cfg.telemetry:
                ring = obs_frames.ring_store(ring,
                                             obs_frames.snapshot(cfg, s))
        return (s, prog, noprog, ring)

    if cfg.telemetry:
        # frame 0 = pass baseline (also guarantees a non-empty ring even
        # for an increment that is quiescent on entry)
        ring0 = obs_frames.ring_store(obs_frames.init_ring(cfg),
                                      obs_frames.snapshot(cfg, st))
    else:
        ring0 = None  # empty pytree: rides the carry at zero cost
    st, _, noprog, ring = jax.lax.while_loop(
        cond, body, (st, st.stat_exec + st.stat_hops, jnp.int32(0), ring0))
    out = (st.cycle - start, quiescent(st), noprog, st.stat_hops,
           st.stat_exec, st.stat_stall, st.stat_allocs)
    if cfg.rhizome_cap > 1:
        out += (st.stat_rhz,)
    return st, out, ring


PALLAS_ON_TPU = (
    "backend='pallas' does not lower on TPU: the cycle megakernel traces "
    "all of cycle_body, whose .at[] updates are scatter/gather ops that "
    "Mosaic refuses ('Unimplemented primitive in Pallas TPU lowering: "
    "scatter'), and it holds the whole MachineState in VMEM (about 1.6 GB "
    "in (8, 128) tiles at chip_32x32_50k), far beyond a TPU core's VMEM. "
    "Use backend='jnp' on TPU; the Pallas backend runs in interpret mode "
    "off-TPU only (DESIGN §6).")


@dataclasses.dataclass
class IncrementResult:
    cycles: int
    active_per_cycle: np.ndarray
    in_flight_per_cycle: np.ndarray
    hops: int
    execs: int
    stalls: int
    allocs: int
    # rhizome protocol (DESIGN §4.5; 0 at rhizome_cap == 1): link
    # requests emitted (OP_LINK_RHIZOME) and OP_RHIZOME_FWD actions run
    rlinks: int = 0
    rfwds: int = 0
    # telemetry frame log (``cfg.telemetry=True`` only, else None): the
    # last ``cfg.frame_ring`` per-chunk frames of each spill pass, read
    # back as one batched transfer per pass (DESIGN §8)
    frames: "obs_frames.FrameLog | None" = None


class StreamingEngine:
    """Host-side driver: the accelerator-style main() of paper Listing 1."""

    def __init__(self, cfg: EngineConfig, app: str | DiffusionApp = "bfs"):
        if cfg.backend == "pallas" and jax.default_backend() == "tpu":
            raise NotImplementedError(PALLAS_ON_TPU)
        self.cfg = cfg
        self.app = APPS[app] if isinstance(app, str) else app
        cfg = dataclasses.replace(cfg, n_vals=self.app.n_vals,
                                  qbatch=self.app.qbatch)
        self.cfg = cfg
        self.state = init_state(cfg, init_vals=self.app.init_val,
                                fwd_init=self.app.fwd_neutral)
        self.total_cycles = 0
        self.totals = dict(hops=0, execs=0, stalls=0, allocs=0, rlinks=0,
                           rfwds=0)
        # resilience bookkeeping (DESIGN §9)
        self.stream_pos = 0        # increments completed == checkpoint step
        self.recovery_log = []     # one dict per livelock recovery attempt
        self._ingest_budget = None  # tm_hiw-gated admission limit

    # -- seeding (e.g. the BFS source vertex gets level 0 pre-stream) --
    def seed(self, vid: int, value: float, val_idx: int = 0):
        """Host-write a value into EVERY rhizome root of ``vid`` so the
        co-equal roots start value-synced (DESIGN §4.5)."""
        cfg = self.cfg
        ks = np.arange(cfg.rhizome_cap)
        r, c, s = rhizome_rcs(cfg, vid, ks)      # [R] each: one scatter
        self.state = self.state._replace(
            vals=self.state.vals.at[vals_index(cfg, r, c, s, q=val_idx)]
            .set(value))

    # -- stream one increment of edges and run to quiescence --
    def run_increment(self, edges: np.ndarray,
                      max_cycles: int | None = None,
                      collect_traces: bool = False,
                      recover=None, ckpt=None,
                      ckpt_block: bool = False) -> IncrementResult:
        """Ingest ``edges`` and run to quiescence.

        ``collect_traces=False`` (default) is the sync-free fast path:
        the whole chunk loop — including the §4.2 livelock detector —
        runs device-side in one jit call per spill pass, and only scalar
        totals come back (``active_per_cycle``/``in_flight_per_cycle``
        are empty).  ``collect_traces=True`` uses the chunked host loop
        and returns the full per-cycle activity traces (jnp chunk
        runner; identical state/totals either way).

        Resilience knobs (DESIGN §9) — both default off, and the
        defaults leave the run bit-identical to the pre-resilience
        driver:

        * ``ckpt`` — a ``train.checkpoint.Checkpointer``: publish a
          durable boundary checkpoint (step = ``stream_pos``) BEFORE
          ingesting this increment.  Default is async, so serialization
          overlaps the device loop below; ``ckpt_block=True`` publishes
          synchronously.  A crash mid-increment restores the boundary
          and replays this increment bit-exactly.
        * ``recover`` — a ``resilience.RecoveryPolicy``: on
          :class:`LivelockError`, roll back to the boundary snapshot,
          escalate lanes/queue_cap per the policy, back off
          exponentially, and retry the increment.  Every attempt is
          appended to ``self.recovery_log`` (with the flight-recorder
          wedge report when telemetry is on); once the budget is spent
          the error re-raises with the attempt log in the message.
          A successful escalation keeps the relieved config for the
          rest of the stream (graceful degradation, not a rollback).

        The whole call runs under the host span ``repro.increment``
        (``inc`` = ``stream_pos``, ``edges``); the spans inside it carry
        the same ``inc`` (DESIGN §8).
        """
        with obs_spans.increment(self.stream_pos, len(edges)):
            if ckpt is not None:
                self.checkpoint(ckpt, block=ckpt_block)
            if recover is None:
                res = self._run_increment(edges, max_cycles, collect_traces)
                self.stream_pos += 1
                return res
            from repro.resilience.recover import migrate_state
            base_cfg = self.cfg
            # the boundary snapshot IS the recovery point: quiescent, so
            # migrate_state can re-seat it under an escalated config
            snapshot = jax.device_get(self.state)
            for attempt in range(recover.max_attempts + 1):
                try:
                    res = self._run_increment(edges, max_cycles,
                                              collect_traces)
                    self.stream_pos += 1
                    return res
                except LivelockError as e:
                    entry = dict(attempt=attempt, cycle=e.cycle, chunk=e.chunk,
                                 lanes=self.cfg.lanes,
                                 queue_cap=self.cfg.queue_cap,
                                 wedge=str(e))
                    self.recovery_log.append(entry)
                    if attempt >= recover.max_attempts:
                        log = "\n".join(
                            f"  attempt {n['attempt']}: lanes={n['lanes']} "
                            f"queue_cap={n['queue_cap']} wedged at cycle "
                            f"{n['cycle']}" for n in self.recovery_log)
                        raise LivelockError(
                            f"{e}\nrecovery budget exhausted "
                            f"({recover.max_attempts} escalations):\n{log}",
                            cycle=e.cycle, chunk=e.chunk,
                            frames=e.frames) from e
                    new_cfg = recover.escalate(base_cfg, attempt + 1)
                    delay = recover.backoff_s * (2 ** attempt)
                    entry["backoff_s"] = delay
                    entry["escalated_to"] = dict(lanes=new_cfg.lanes,
                                                 queue_cap=new_cfg.queue_cap)
                    if delay:
                        time.sleep(delay)
                    self.cfg = new_cfg
                    self.state = migrate_state(new_cfg, self.app, snapshot)
                    self._ingest_budget = None  # re-learn under the new sizing

    def _run_increment(self, edges, max_cycles, collect_traces):
        cfg = self.cfg
        limit = max_cycles or cfg.max_cycles
        self.state, spill = load_stream(cfg, self.state, edges,
                                        limit=self._ingest_limit())
        with obs_spans.span("repro.reset_counters"):
            self._reset_counters()
        if collect_traces:
            return self._run_increment_traced(spill, limit)
        rings = []
        cycles, q, noprog, counters, spill = self._device_passes(
            cfg, spill, limit, rings)
        frames = obs_frames.FrameLog.from_rings(rings) if rings else None
        if not q and noprog >= LIVELOCK_CHUNKS:
            # Message-dependent-deadlock detector: YX DOR keeps the
            # NETWORK acyclic, but the execute stage (pop -> emit ->
            # channel) can close a protocol cycle when buffers are sized
            # below the workload's dependency depth.  Fail loudly with
            # sizing advice — and the flight recorder's wedge report when
            # telemetry is on — instead of silently dropping work.
            _raise_livelock(cfg, cycle=cycles, chunk=cycles // cfg.chunk,
                            frames=frames)
        if len(spill):
            raise RuntimeError(self._spill_msg(limit, spill))
        if cfg.faults is not None:
            cycles = self._repair_rounds(limit, cycles, rings)
            counters = self._counters()
            frames = (obs_frames.FrameLog.from_rings(rings)
                      if rings else None)
        if cfg.ingest_guard:
            # learn the admission budget for the NEXT increment from this
            # increment's action-queue hi-water marks
            self._update_ingest_budget()
        return self._finish_increment(
            cycles, counters,
            np.zeros(0, np.int32), np.zeros(0, np.int32), frames)

    def _counters(self) -> tuple:
        """The increment's counters as ``_finish_increment`` takes them:
        (hops, execs, stalls, allocs, rlinks, rfwds), read from the
        state."""
        st = self.state
        rhz = (st.stat_rhz[RZ_LINK], st.stat_rhz[RZ_FWD]) \
            if self.cfg.rhizome_cap > 1 else (0, 0)
        return tuple(int(x) for x in jax.device_get((
            st.stat_hops, st.stat_exec, st.stat_stall, st.stat_allocs,
            *rhz)))

    def _reset_counters(self):
        """Zero the per-increment counters before the device loop."""
        cfg = self.cfg
        self.state = self.state._replace(stat_hops=jnp.int32(0),
                                         stat_exec=jnp.int32(0),
                                         stat_stall=jnp.int32(0),
                                         stat_allocs=jnp.int32(0))
        if cfg.rhizome_cap > 1:
            self.state = self.state._replace(
                stat_rhz=jnp.zeros_like(self.state.stat_rhz))
        if cfg.qbatch > 1:
            # per-query relax counters reset per increment so the mq
            # session layer reads them as this-increment activity (§10);
            # qlast persists — it is the absolute settle cycle per slot
            self.state = self.state._replace(
                qchg=jnp.zeros_like(self.state.qchg))
        if cfg.faults is not None:
            # fault counters reset with the stat_* scalars: the §9 loss
            # detector reconciles per increment
            self.state = self.state._replace(
                flt=jnp.zeros_like(self.state.flt))
        if cfg.telemetry:
            # the telemetry planes reset with the stat_* scalars so the
            # final frame of the increment reconciles exactly (DESIGN §8)
            self.state = self.state._replace(
                tm_cell=jnp.zeros_like(self.state.tm_cell),
                tm_lane=jnp.zeros_like(self.state.tm_lane),
                tm_hiw=jnp.zeros_like(self.state.tm_hiw))

    def _device_passes(self, cfg, spill, limit, rings, cycles=0):
        """Sync-free device passes until quiescence with the spill fully
        drained, or until the cycle/livelock budget trips.  Returns
        ``(cycles, quiescent, noprog, (hops, execs, stalls, allocs,
        rlinks, rfwds), spill)`` — counters are the increment-cumulative
        stat scalars.
        Each pass runs under the host spans ``repro.dispatch`` (the
        device loop's call) and ``repro.wait`` (its readback, where the
        host waits for the device)."""
        while True:
            with obs_spans.span("repro.dispatch"):
                self.state, out, ring = _increment_device_loop(
                    cfg, self.app, self.state, limit - cycles)
            # exactly ONE batched transfer per pass: the scalar record
            # and the frame ring come back together
            with obs_spans.span("repro.wait"):
                out, ring = jax.device_get((out, ring))
            ran, q, noprog, *counters = (int(x) for x in out[:7])
            rhz = out[7] if cfg.rhizome_cap > 1 else (0, 0)
            counters = (*counters, int(rhz[RZ_LINK]), int(rhz[RZ_FWD]))
            if ring is not None:
                rings.append(ring)
            cycles += ran
            if q and len(spill):
                # io_stream_cap overflow residue: the loaded prefix is
                # fully consumed at quiescence, so the next pass has the
                # whole IO capacity again (DESIGN §4.2)
                if cfg.ingest_guard:
                    self._update_ingest_budget()
                self.state, spill = load_stream(cfg, self.state, spill,
                                                limit=self._ingest_limit())
                continue
            break
        return cycles, q, noprog, counters, spill

    def _run_increment_traced(self, spill, limit) -> IncrementResult:
        """Chunked host loop with per-cycle activity traces (the original
        driver); used when ``collect_traces=True``."""
        cfg = self.cfg
        act, flt = [], []
        cycles = 0
        last_exec, no_progress = 0, 0
        ring = None
        if cfg.telemetry:
            # same frame schema as the device loop, snapshotted eagerly
            # per chunk (this is the debug path — syncs are fine here)
            ring = obs_frames.ring_store(obs_frames.init_ring(cfg),
                                         obs_frames.snapshot(cfg, self.state))
        while cycles < limit:
            self.state, stats = run_chunk(cfg, self.app, self.state)
            if cfg.telemetry:
                ring = obs_frames.ring_store(
                    ring, obs_frames.snapshot(cfg, self.state))
            q = np.asarray(stats.quiescent)
            a = np.asarray(stats.active)
            f = np.asarray(stats.in_flight)
            if q.any():
                n = int(np.argmax(q))  # first quiescent cycle in chunk
                act.append(a[:n]); flt.append(f[:n])
                cycles += n
                if len(spill):
                    self.state, spill = load_stream(cfg, self.state, spill)
                    continue
                break
            act.append(a); flt.append(f)
            cycles += cfg.chunk
            e = int(self.state.stat_exec) + int(self.state.stat_hops)
            no_progress = no_progress + 1 if e == last_exec else 0
            last_exec = e
            if no_progress >= LIVELOCK_CHUNKS:
                frames = (obs_frames.FrameLog.from_rings(
                    [jax.device_get(ring)]) if ring is not None else None)
                _raise_livelock(cfg, cycle=cycles,
                                chunk=cycles // cfg.chunk, frames=frames)
        if len(spill):
            raise RuntimeError(self._spill_msg(limit, spill))
        if cfg.faults is not None:
            # debug path reuses the device-loop repair passes (per-cycle
            # traces cover the faulty run; the repair tail is untraced)
            cycles = self._repair_rounds(limit, cycles, [])
        if cfg.ingest_guard:
            self._update_ingest_budget()
        frames = (obs_frames.FrameLog.from_rings([jax.device_get(ring)])
                  if ring is not None else None)
        return self._finish_increment(
            cycles, self._counters(),
            np.concatenate(act) if act else np.zeros(0, np.int32),
            np.concatenate(flt) if flt else np.zeros(0, np.int32), frames)

    # -- detection + repair: the §8 invariants as a loss detector (§9) --

    def _loss_count(self) -> int:
        """Messages lost this increment: the injected-fault counters,
        cross-checked (when telemetry is on) against the §8 conservation
        invariant — link departures (``stat_hops``) minus link deliveries
        (sum of the ``TM_HOP`` plane) is exactly the drop count, with no
        reference to the injection bookkeeping."""
        from repro.resilience.faults import FLT_CORRUPT, FLT_DROP
        flt = np.asarray(jax.device_get(self.state.flt))
        lost = int(flt[FLT_DROP]) + int(flt[FLT_CORRUPT])
        if self.cfg.telemetry:
            gap = int(self.state.stat_hops) - int(
                np.asarray(self.state.tm_cell)[..., TM_HOP].sum())
            lost = max(lost, gap + int(flt[FLT_CORRUPT]))
        return lost

    def _repair_entries(self) -> np.ndarray:
        """Stream rows re-injecting every finite durable value at every
        active rhizome root of its vertex: ``(vid, -(k+1), value_bits)``
        sentinel rows (negative dst => OP_REPAIR, see io_stage).  The
        forced re-diffusion of all of them, run to quiescence over the
        intact edge storage, is one full monotone relaxation sweep from
        correct sources — it reaches the exact fixpoint in a single
        fault-free round (DESIGN §9)."""
        cfg, app = self.cfg, self.app
        vids = np.arange(cfg.n_vertices, dtype=np.int64)[None, :]
        ks = np.arange(cfg.rhizome_cap, dtype=np.int64)[:, None]
        r, c, s = rhizome_rcs(cfg, vids, ks)                     # [R, n]
        vals = np.asarray(self.state.vals[vals_index(cfg, ...)])[r, c, s]
        on = np.asarray(self.state.rhz_on)[r, c, s]
        on[0, :] = True                # canonical root is always live
        v = functools.reduce(app.combine, vals)                  # [n]
        tgt = on & (v != np.float32(app.init_val))[None, :]
        kk, vv = np.nonzero(tgt)
        bits = np.ascontiguousarray(
            v[vv].astype(np.float32)).view(np.int32)
        return np.stack([vv.astype(np.int32),
                         (-(kk + 1)).astype(np.int32), bits],
                        axis=1).astype(np.int32)

    def _repair_rounds(self, limit, cycles, rings) -> int:
        """Bounded graceful-degradation pass: when the loss detector
        fires at end of increment, re-inject the durable values as
        OP_REPAIR traffic and re-run to quiescence under the plan's
        zero-rate twin (``FaultPlan.safe()`` — recovery rides a reliable
        transport, and the twin keeps every leaf shape so the state
        flows into the repair jit without reshaping)."""
        cfg = self.cfg
        plan = cfg.faults
        if self._loss_count() == 0:
            return cycles
        safe_cfg = dataclasses.replace(cfg, faults=plan.safe())
        for _ in range(plan.max_repair_rounds):
            before = self._loss_count()
            entries = self._repair_entries()
            if not len(entries):
                break                  # nothing durable to re-diffuse
            self.state, spill = load_stream(cfg, self.state, entries)
            cycles, q, noprog, _, spill = self._device_passes(
                safe_cfg, spill, limit, rings, cycles)
            if not q and noprog >= LIVELOCK_CHUNKS:
                _raise_livelock(
                    safe_cfg, cycle=cycles, chunk=cycles // cfg.chunk,
                    frames=(obs_frames.FrameLog.from_rings(rings)
                            if rings else None))
            if len(spill):
                raise RuntimeError(self._spill_msg(limit, spill))
            if self._loss_count() == before:
                break                  # clean round: fixpoint reached
        else:
            raise RuntimeError(
                f"repair budget exhausted: {plan.max_repair_rounds} "
                "rounds each lost messages — the repair transport is "
                "expected to be fault-free (FaultPlan.safe()); see "
                "DESIGN.md §9")
        return cycles

    # -- ingest guard: tm_hiw-gated admission (DESIGN §9) --

    def _ingest_limit(self) -> int | None:
        return self._ingest_budget if self.cfg.ingest_guard else None

    def _update_ingest_budget(self) -> None:
        """AIMD-style admission control from the action-queue hi-water
        telemetry: halve the per-load admission budget when any cell's AQ
        crested within the reserve band of ``queue_cap`` (the §4.2
        pre-wedge signature), double it back while the fabric runs below
        half the band."""
        cfg = self.cfg
        ceiling = cfg.queue_cap - cfg.aq_reserve - cfg.sys_reserve
        cap = cfg.io_cells * cfg.io_stream_cap
        hiw = int(np.asarray(jax.device_get(
            self.state.tm_hiw))[..., TM_HW_AQ].max())
        cur = cap if self._ingest_budget is None else self._ingest_budget
        if hiw >= ceiling:
            cur = max(cfg.io_cells, cur // 2)
        elif hiw < max(1, ceiling // 2):
            cur = min(cap, cur * 2)
        self._ingest_budget = cur

    # -- durable state: boundary checkpoint / restore (DESIGN §9) --

    def checkpoint(self, ckpt, step: int | None = None,
                   block: bool = True) -> int:
        """Publish the full machine pytree + stream cursor + config
        fingerprint through ``ckpt`` (a ``train.checkpoint.
        Checkpointer``).  Only sound at an increment boundary (which is
        where ``run_increment(ckpt=...)`` calls it).  ``block=False``
        snapshots to host and serializes on the writer thread."""
        from repro.resilience.checkpoint import stream_manifest
        step = self.stream_pos if step is None else step
        save = ckpt.save if block else ckpt.save_async
        save(step, self.state._asdict(), extra=stream_manifest(self))
        return step

    @classmethod
    def restore(cls, cfg: EngineConfig, app, ckpt,
                step: int | None = None, shardings=None,
                strict: bool = True, verify: bool = True):
        """Rebuild an engine from a boundary checkpoint: replaying the
        remaining stream from ``engine.stream_pos`` reproduces the
        uninterrupted run bit-exactly.  ``shardings`` may be a
        ``MachineState`` of NamedShardings (e.g. ``cca_state_shardings``)
        for elastic re-sharding onto the current mesh."""
        from repro.resilience.checkpoint import config_fingerprint
        eng = cls(cfg, app)
        like = jax.tree.map(np.asarray, eng.state._asdict())
        sh = (shardings._asdict() if isinstance(shardings, MachineState)
              else shardings)
        tree, extra, step = ckpt.restore(like, step=step, shardings=sh,
                                         verify=verify)
        if strict:
            fp = config_fingerprint(eng.cfg)
            if extra.get("config") != fp:
                raise ValueError(
                    f"checkpoint step {step} was saved under config "
                    f"{extra.get('config')}, engine is {fp}: restoring "
                    "across configs would reinterpret the address/queue "
                    "layout silently (strict=False only for post-mortem "
                    "inspection)")
            if extra.get("app") != eng.app.name:
                raise ValueError(
                    f"checkpoint app '{extra.get('app')}' != engine app "
                    f"'{eng.app.name}'")
        if sh is None:
            tree = {k: jnp.asarray(v) for k, v in tree.items()}
        eng.state = MachineState(**tree)
        eng.stream_pos = int(extra.get("stream_pos", step))
        eng.total_cycles = int(extra.get("total_cycles", 0))
        eng.totals.update({k: int(v) for k, v in
                           extra.get("totals", {}).items()})
        return eng

    def _spill_msg(self, limit, spill) -> str:
        # never drop work silently: the cycle limit ran out before the
        # spilled residue could be re-loaded and ingested
        return (f"cycle limit {limit} exhausted with {len(spill)} spilled "
                "edges not yet ingested; raise max_cycles or io_stream_cap "
                "(DESIGN.md §4.2).")

    def _finish_increment(self, cycles, counters, act, flt,
                          frames=None) -> IncrementResult:
        """``counters``: (hops, execs, stalls, allocs, rlinks, rfwds)."""
        self.total_cycles += cycles
        named = dict(zip(("hops", "execs", "stalls", "allocs", "rlinks",
                          "rfwds"), counters))
        for k, v in named.items():
            self.totals[k] += v
        return IncrementResult(
            cycles=cycles, active_per_cycle=act, in_flight_per_cycle=flt,
            frames=frames, **named)

    # -- read back application values from the vertex objects --
    def values(self, n: int | None = None, val_idx: int = 0,
               combine=None) -> np.ndarray:
        """Min-reduce over every rhizome root of each vertex.

        The canonical root always holds the tightest value (all external
        relaxes land there; siblings only receive its snapshots), so for
        the bundled monotone-min apps the reduce equals the canonical
        value — kept as a reduce so readback stays correct even mid-run.

        ``combine`` overrides the app-level root reduce — a qbatch
        composite passes the PER-SLOT combine of the query living in
        ``val_idx`` (repro.mq readback, DESIGN §10).
        """
        cfg = self.cfg
        n = n or cfg.n_vertices
        # one batched gather over all (root k, vertex) pairs instead of a
        # python loop of per-k fancy indexing
        vids = np.arange(n, dtype=np.int64)[None, :]
        ks = np.arange(cfg.rhizome_cap, dtype=np.int64)[:, None]
        r, c, s = rhizome_rcs(cfg, vids, ks)                     # [R, n]
        v = np.asarray(self.state.vals[vals_index(cfg, ..., q=val_idx)])[
            r, c, s]
        return functools.reduce(combine or self.app.combine, v)

    def vertex_object_stats(self) -> dict:
        """Diagnostics over the hierarchical vertex objects: ghost usage +
        locality (validates Fig. 5 policies) plus rhizome fan-out and the
        spread of co-equal roots over the mesh (DESIGN §4.5)."""
        cfg = self.cfg
        st = self.state
        gs = np.asarray(st.gstate)
        ga = np.asarray(st.gaddr)
        used = int(np.sum(np.asarray(st.nfree) - cfg.primary_slots))
        out = dict(ghosts=used, mean_hops=0.0, max_hops=0,
                   rhizomes=0, multi_root_vertices=0, max_fanout=1,
                   mean_rhizome_hops=0.0)
        have = gs == 2
        if have.any():
            rr, cc, _ = np.nonzero(have)
            tgt_cell = ga[have] // cfg.slots
            tr, tc = tgt_cell // cfg.width, tgt_cell % cfg.width
            d = np.abs(rr - tr) + np.abs(cc - tc)
            out.update(mean_hops=float(d.mean()), max_hops=int(d.max()))
        if cfg.rhizome_cap > 1:
            on = np.asarray(st.rhz_on)          # [H,W,S]
            # batched gather over all (root k, vertex) pairs (no per-k
            # python loop): rows 1.. are the secondary roots
            vids = np.arange(cfg.n_vertices, dtype=np.int64)[None, :]
            ks = np.arange(cfg.rhizome_cap, dtype=np.int64)[:, None]
            r, c, s = rhizome_rcs(cfg, vids, ks)                 # [R, n]
            act = on[r, c, s][1:]                                # [R-1, n]
            fan = 1 + act.sum(axis=0)
            d = np.abs(r[1:] - r[0]) + np.abs(c[1:] - c[0])      # [R-1, n]
            out.update(
                rhizomes=int(fan.sum() - cfg.n_vertices),
                multi_root_vertices=int((fan > 1).sum()),
                max_fanout=int(fan.max()),
                mean_rhizome_hops=(float(d[act].mean())
                                   if act.any() else 0.0))
        return out
