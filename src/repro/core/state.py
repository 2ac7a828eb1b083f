"""Machine state: the whole AM-CCA chip as one fixed-shape pytree.

Slot layout per cell: slots ``[0, P)`` with ``P = rhizome_cap * root_slots``
are the statically partitioned rhizome-root region — slot
``k * root_slots + j`` is rhizome root ``k`` of the vertex with local index
``j`` (root 0 at cell ``v % n_cells`` is the classic canonical RPVO root).
Slots ``[P, S)`` are ghost slots handed out by the allocator.  A global
address is ``addr = cell * S + slot`` (int32).

Secondary rhizome roots (k >= 1) start *inactive* (``rhz_on`` False) and are
grown on demand by the OP_LINK_RHIZOME protocol (DESIGN §4.5): an insert
arriving at an inactive root is deferred on the slot's future queue exactly
like the ghost G_PENDING protocol, and drains when the canonical root's
value-carrying OP_RHIZOME_FWD ack activates the slot.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import EngineConfig
from repro.core.msg import N_DIRS

# ghost-future states (paper Fig. 4)
G_NULL, G_PENDING, G_SET = 0, 1, 2

INF = jnp.float32(1e9)

# ---- telemetry plane indices (repro.obs, DESIGN §8) ----
# Per-cell per-stage activity counts, ``tm_cell [H, W, N_TM_STAGES]``.
# The counts are CUMULATIVE over an increment (reset with the stat_*
# scalars) so the final plane reconciles exactly with the scalar
# counters: sum(TM_HOP) == stat_hops, sum(TM_EXEC) == stat_exec at
# quiescence, sum(TM_STALL) + sum(TM_PARK) == stat_stall,
# sum(TM_ALLOC) == stat_allocs.
TM_EXEC = 0     # actions popped by phase0 (== completed at quiescence)
TM_ALLOC = 1    # ghost allocations served here
TM_STALL = 2    # staging backpressure stalls + phase0 head rotations
TM_HOP = 3      # flits accepted into this cell by the hop stage
TM_STAGE = 4    # emissions staged successfully (network or local queue)
TM_PARK = 5     # remote emissions parked (lane full at staging time)
TM_UNPARK = 6   # parked messages re-injected into a lane
TM_IO = 7       # streamed edge inserts accepted at this IO cell
TM_BCAST = 8    # rhizome sibling broadcasts staged (fan-out traffic)
N_TM_STAGES = 9

# Per-link per-lane counters, ``tm_lane [H, W, 4, L, N_TM_LANE]``.
TM_L_OCC = 0    # sum of lane occupancy per cycle (avg depth = OCC/cycles)
TM_L_GRANT = 1  # arbiter grants won AND accepted (== hops on this lane)
TM_L_BLOCK = 2  # cycles the lane was occupied but not granted
N_TM_LANE = 3

# Per-cell hi-water marks, ``tm_hiw [H, W, N_TM_HIW]``.
TM_HW_AQ = 0    # action-queue depth hi-water
TM_HW_PK = 1    # park-ring depth hi-water
N_TM_HIW = 2

# Rhizome-protocol counters (DESIGN §4.5), ``stat_rhz [N_RZ]`` when
# rhizome_cap > 1, summed over an increment like the stat_* scalars.
RZ_LINK = 0     # OP_LINK_RHIZOME emitted: an insert reached a secondary
                # root that had neither been activated nor asked to be
RZ_FWD = 1      # OP_RHIZOME_FWD actions executed (sibling broadcasts
                # and link acks)
N_RZ = 2


def tail_pending(cfg) -> int:
    """``tcnt`` of a root whose request for a new chain tail is out
    (``cfg.tail_insert``): past ``edge_cap``, so no insert goes to the old
    tail meanwhile."""
    return cfg.edge_cap + 1


class MachineState(NamedTuple):
    # --- RPVO slot storage [H, W, S, ...] ---
    vals: jax.Array        # [H,W,S]    f32  application values (BFS level,
                           # ...); [H,W,S,Q] at qbatch > 1 (vals_index)
    nedges: jax.Array      # [H,W,S]    i32  edges in this RPVO node
    edst: jax.Array        # [H,W,S,E]  i32  edge dst = root addr of dst vertex
    ew: jax.Array          # [H,W,S,E]  f32  edge weight
    gaddr: jax.Array       # [H,W,S]    i32  ghost address (-1 if none)
    gstate: jax.Array      # [H,W,S]    i32  future state: null/pending/set
    rhz_on: jax.Array      # [H,W,S]    bool secondary rhizome root is active
    rstate: jax.Array      # [H,W,S]    i32  rhizome-link state (G_* codes)
    nfree: jax.Array       # [H,W]      i32  next free ghost slot
    # --- future LCO deferred queues [H,W,S,FQ,3]: (op, arg0, arg1) ---
    fq: jax.Array
    fq_n: jax.Array        # [H,W,S] i32
    fq_head: jax.Array     # [H,W,S] i32
    # --- coalesced deferred app-forward (futures merge monotone relaxes) ---
    fwd_val: jax.Array     # [H,W,S] f32
    fwd_pending: jax.Array # [H,W,S] bool
    # --- per-cell action queue ---
    aq: jax.Array          # [H,W,Q,MSG] i32
    aq_n: jax.Array        # [H,W] i32
    aq_head: jax.Array     # [H,W] i32
    # --- per-cell, per-direction outgoing channels, lane-major (§7):
    #     each physical link carries cfg.lanes independently-queued
    #     virtual lanes of cfg.lane_capacity messages each ---
    ch: jax.Array          # [H,W,4,L,LC,MSG] i32
    ch_n: jax.Array        # [H,W,4,L] i32
    ch_head: jax.Array     # [H,W,4,L] i32
    ch_rr: jax.Array       # [H,W,4] i32  round-robin lane-arbiter pointer
    # --- per-cell park buffer (§7): stalled remote emissions store here
    #     (separate from the action queue so in-transit messages never
    #     hold it above the admission thresholds); lanes=1 -> 1-deep dummy
    pk: jax.Array          # [H,W,PK,MSG] i32
    pk_n: jax.Array        # [H,W] i32
    pk_head: jax.Array     # [H,W] i32
    # --- active-action registers (serialized execute/propagate; 1 op/cycle) ---
    cmsg: jax.Array        # [H,W,MSG] i32
    cvalid: jax.Array      # [H,W] bool
    cphase: jax.Array      # [H,W] i32   emissions staged so far + 1
    cT: jax.Array          # [H,W] i32   total emissions of the active action
    cemit: jax.Array       # [H,W] f32   snapshot of the emission source value
    cout: jax.Array        # [H,W,MSG] i32 precomputed single emission
    cdrain: jax.Array      # [H,W] i32   deferred-queue drains of active action
    # --- IO cells (streaming ingestion) ---
    io_edges: jax.Array    # [IO, L, 3] i32 (src vid, dst vid, weight bits)
    io_n: jax.Array        # [IO] i32 edges loaded
    io_pos: jax.Array      # [IO] i32 cursor
    # --- allocator rotation counters ---
    arot: jax.Array        # [H,W] i32
    # --- cycle counters / stats (per-chunk, host-accumulated) ---
    cycle: jax.Array       # scalar i32
    stat_hops: jax.Array   # scalar i32 (reset per chunk; host accumulates)
    stat_exec: jax.Array   # scalar i32 actions completed
    stat_stall: jax.Array  # scalar i32 staging stalls
    stat_allocs: jax.Array # scalar i32 ghost allocations
    # --- telemetry planes (repro.obs, DESIGN §8): accumulated inside the
    #     cycle stages when cfg.telemetry, snapshotted per chunk into the
    #     on-device frame ring; 1x1-shaped dummies (never touched) when
    #     telemetry is off so the off path stays bit-exact and free ---
    tm_cell: jax.Array     # [H,W,N_TM_STAGES] i32 per-cell stage activity
    tm_lane: jax.Array     # [H,W,4,L,N_TM_LANE] i32 lane occ/grant/blocked
    tm_hiw: jax.Array      # [H,W,N_TM_HIW] i32 AQ / park-ring hi-water
    # --- fault-injection counters (repro.resilience, DESIGN §9):
    #     [N_FLT] i32 (FLT_* indices in resilience/faults.py) when
    #     cfg.faults is set, else a [1] dummy — same pattern as the
    #     telemetry planes, so faults=None stays bit-exact and the
    #     Pallas megakernel carries the leaf through its generic
    #     flattening with zero kernel changes ---
    flt: jax.Array
    # --- per-query quiescence counters (repro.mq, DESIGN §10): when
    #     cfg.qbatch > 1, qchg[q] counts relax changes of query slot q
    #     (reset per increment with the stat_* scalars) and qlast[q]
    #     holds the machine cycle of slot q's last change — the per-slot
    #     changed-bits folded into the stat record that mq/session.py
    #     reads for per-query time-to-quiescence and slot retirement.
    #     [1] dummies (never touched) when qbatch == 1 ---
    qchg: jax.Array        # [Q] i32 (or [1] dummy)
    qlast: jax.Array       # [Q] i32 (or [1] dummy)
    # --- rhizome-protocol counters (RZ_* indices, DESIGN §4.5): reset
    #     per increment with the stat_* scalars when cfg.rhizome_cap > 1;
    #     a [1] dummy (never touched) at rhizome_cap == 1, so the
    #     single-root machine traces none of it ---
    stat_rhz: jax.Array    # [N_RZ] i32 (or [1] dummy)
    # --- chain tail of each root (cfg.tail_insert, DESIGN §4.6): the
    #     address of the root's last ghost and the edges the root has sent
    #     it (``tail_pending(cfg)`` while the root's request for the next
    #     ghost is out); meaningful at primary slots only.  [1,1,1]
    #     dummies (never touched) when tail_insert is off ---
    gtail: jax.Array       # [H,W,S] i32 (or [1,1,1] dummy)
    tcnt: jax.Array        # [H,W,S] i32 (or [1,1,1] dummy)


def init_state(cfg: EngineConfig,
               init_vals: float | np.ndarray = 1e9,
               fwd_init: float | np.ndarray = 1e9) -> MachineState:
    """Fresh machine: all vertices allocated as roots, no edges, empty queues.

    ``init_vals`` may be a ``[n_vals]`` vector (per-query init values when
    ``cfg.qbatch > 1``); ``fwd_init`` is the neutral element of the
    coalescing forward register (``app.fwd_neutral`` — 1e9 for the
    min-monotone apps), likewise scalar or per-query.
    """
    cfg.validate()
    H, W, S, E = cfg.height, cfg.width, cfg.slots, cfg.edge_cap
    FQ, Q = cfg.futq_cap, cfg.queue_cap
    VL, LC = cfg.lanes, cfg.lane_capacity
    IO, L = cfg.io_cells, cfg.io_stream_cap
    QB, WM = cfg.qbatch, cfg.msg_words
    z32 = lambda *s: jnp.zeros(s, jnp.int32)
    # qbatch > 1 widens the vertex values, the emission snapshot and the
    # forward register with the query axis (DESIGN §10); qbatch == 1
    # keeps scalar shapes, so no size-1 value axis lands on the TPU's
    # 128 lanes (a 128-fold padded copy of vals every cycle)
    fwd_shape = (H, W, S) if QB == 1 else (H, W, S, QB)
    cemit_shape = (H, W) if QB == 1 else (H, W, QB)
    return MachineState(
        vals=jnp.full(fwd_shape, jnp.float32(init_vals)),
        nedges=z32(H, W, S),
        edst=jnp.full((H, W, S, E), -1, jnp.int32),
        ew=jnp.zeros((H, W, S, E), jnp.float32),
        gaddr=jnp.full((H, W, S), -1, jnp.int32),
        gstate=z32(H, W, S),
        rhz_on=jnp.zeros((H, W, S), bool),
        rstate=z32(H, W, S),
        nfree=jnp.full((H, W), cfg.primary_slots, jnp.int32),
        fq=z32(H, W, S, FQ, 3),
        fq_n=z32(H, W, S), fq_head=z32(H, W, S),
        fwd_val=jnp.full(fwd_shape, jnp.float32(fwd_init)),
        fwd_pending=jnp.zeros((H, W, S), bool),
        aq=z32(H, W, Q, WM), aq_n=z32(H, W), aq_head=z32(H, W),
        ch=z32(H, W, N_DIRS, VL, LC, WM),
        ch_n=z32(H, W, N_DIRS, VL), ch_head=z32(H, W, N_DIRS, VL),
        ch_rr=z32(H, W, N_DIRS),
        pk=z32(H, W, cfg.park_capacity, WM),
        pk_n=z32(H, W), pk_head=z32(H, W),
        cmsg=z32(H, W, WM),
        cvalid=jnp.zeros((H, W), bool),
        cphase=z32(H, W), cT=z32(H, W),
        cemit=jnp.zeros(cemit_shape, jnp.float32),
        cout=z32(H, W, WM),
        cdrain=z32(H, W),
        io_edges=z32(IO, L, 3), io_n=z32(IO), io_pos=z32(IO),
        arot=z32(H, W),
        cycle=jnp.int32(0), stat_hops=jnp.int32(0), stat_exec=jnp.int32(0),
        stat_stall=jnp.int32(0), stat_allocs=jnp.int32(0),
        tm_cell=z32(*((H, W) if cfg.telemetry else (1, 1)), N_TM_STAGES),
        tm_lane=z32(*((H, W, N_DIRS, VL) if cfg.telemetry
                      else (1, 1, 1, 1)), N_TM_LANE),
        tm_hiw=z32(*((H, W) if cfg.telemetry else (1, 1)), N_TM_HIW),
        flt=z32(4 if cfg.faults is not None else 1),
        qchg=z32(QB if QB > 1 else 1),
        qlast=z32(QB if QB > 1 else 1),
        stat_rhz=z32(N_RZ if cfg.rhizome_cap > 1 else 1),
        gtail=jnp.full((H, W, S) if cfg.tail_insert else (1, 1, 1), -1,
                       jnp.int32),
        tcnt=z32(*((H, W, S) if cfg.tail_insert else (1, 1, 1))),
    )


# ---------------- addressing helpers ----------------

def vals_index(cfg: EngineConfig, *lead, q: int = 0) -> tuple:
    """Index of query ``q``'s values in the ``vals`` leaf: ``lead``
    alone at qbatch == 1, where ``vals`` is ``[H,W,S]``, and ``lead``
    plus ``q`` above, where it is ``[H,W,S,Q]``.  ``vals[vals_index(cfg,
    ..., q=q)]`` is query q's ``[H,W,S]`` plane; ``vals.at[vals_index(cfg,
    r, c, s, q=q)]`` its value at (r, c, s)."""
    return lead if cfg.qbatch == 1 else (*lead, q)


def root_addr(cfg: EngineConfig, vid):
    """Global address of vertex vid's RPVO root."""
    vid = jnp.asarray(vid, jnp.int32)
    cell = vid % cfg.n_cells
    slot = vid // cfg.n_cells
    return cell * cfg.slots + slot


def addr_cell(cfg: EngineConfig, addr):
    return addr // cfg.slots


def addr_slot(cfg: EngineConfig, addr):
    return addr % cfg.slots


def cell_rc(cfg: EngineConfig, cell):
    return cell // cfg.width, cell % cfg.width


def self_cell_grid(cfg: EngineConfig):
    """[H,W] array of flat cell ids."""
    return (jnp.arange(cfg.height, dtype=jnp.int32)[:, None] * cfg.width
            + jnp.arange(cfg.width, dtype=jnp.int32)[None, :])
