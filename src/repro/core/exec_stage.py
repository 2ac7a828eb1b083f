"""Action execution: the diffusive programming model's compute stage.

AM-CCA executes **one operation per cell per cycle**: either a computing
instruction (the action body) or the creation/staging of one new message
via ``propagate`` (paper §4).  We model this faithfully with per-cell
active-action registers: an action occupies its cell for ``1 + T`` cycles —
one mutate cycle (phase 0) plus one cycle per emission, with backpressure
stalls when the target buffer is full.

Handlers implemented (paper Listings 4-6 + system actions of Fig. 3/4,
plus the rhizome protocol of DESIGN §4.5):

  OP_INSERT_EDGE  insert-edge-action with the full ghost/future protocol;
                  at an inactive rhizome root it defers on the slot's
                  future queue and requests activation (OP_LINK_RHIZOME)
  OP_APP          the application action (bfs-action et al.); a changed
                  relax at a canonical root with linked siblings broadcasts
                  OP_RHIZOME_FWD to every co-equal root in parallel
  OP_ALLOC        remote ghost allocation (vicinity/random allocator)
  OP_SET_FUTURE   continuation return: set future, drain deferred queue
  OP_RHIZOME_FWD  sibling value sync: relax locally, diffuse along the
                  local edge shard + own ghost chain; activates a pending
                  rhizome root and drains its deferred inserts (link-ack)
  OP_LINK_RHIZOME activation request at the canonical root: mark the
                  vertex multi-root and ack with the current value

Implementation note (§Perf, cca cell): slot writes and the small per-slot
reads are one-hot ``where``s over the slot axis — never a scatter — so
GSPMD partitions each cycle over the sharded cell grid with zero
collectives beyond the routing permutes and the quiescence all-reduce.
Staging's reads of one element per cell from the large slot leaves
(``edst``/``ew`` at ``[slot, edge]``, ``fq`` at ``[slot, head]``) are
gathers batched over the cell grid (:func:`take_cell`): they partition
over the grid with no collectives either, and read a few words per cell
where a one-hot reduce would read the whole leaf and reduce its slot axis
across the TPU's 128 lanes every cycle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import rings
from repro.core.alloc import (choose_alloc_cell, rhizome_addr,
                              rhizome_owner_vid)
from repro.core.apps import DiffusionApp, neutral_vec
from repro.core.config import EngineConfig
from repro.core.msg import (MSG_WORDS, OP_ALLOC, OP_APP, OP_INSERT_EDGE,
                            OP_LINK_RHIZOME, OP_REPAIR, OP_RHIZOME_FWD,
                            OP_SET_FUTURE, TB_AQ_SELF, f2i, i2f, make_msg,
                            make_qmsg, msg_qvals, msg_seal, pad_msg,
                            qsel_mask, seal_msg)
from repro.core.routing import deliver, msg_lane, yx_target_buffer
from repro.core.state import (G_NULL, G_PENDING, G_SET, RZ_FWD, RZ_LINK,
                              MachineState, TM_ALLOC, TM_BCAST, TM_EXEC,
                              TM_PARK, TM_STAGE, TM_STALL, tail_pending)


def _oh(idx, n, mask=None):
    """One-hot [..., n] selector; optionally masked."""
    oh = jnp.arange(n, dtype=jnp.int32) == idx[..., None]
    if mask is not None:
        oh = oh & mask[..., None]
    return oh


def _expand(oh, arr):
    """Reshape a [H,W,S] selector to broadcast against arr [H,W,S,...]."""
    return oh.reshape(oh.shape + (1,) * (arr.ndim - oh.ndim))


def sel(arr, slot):
    """arr[II, JJ, slot] as one-hot reduce.  arr: [H,W,S,...] -> [H,W,...]."""
    oh = _expand(_oh(slot, arr.shape[2]), arr)
    if arr.dtype == jnp.bool_:
        return jnp.any(oh & arr, axis=2)
    return jnp.sum(jnp.where(oh, arr, 0), axis=2).astype(arr.dtype)


def put(arr, slot, val, mask):
    """arr[II, JJ, slot] = val where mask.  val: [H,W,...] or scalar."""
    oh = _expand(_oh(slot, arr.shape[2], mask), arr)
    val = jnp.asarray(val, arr.dtype)
    if val.ndim >= 2 and val.shape[:2] == arr.shape[:2]:
        val = jnp.expand_dims(val, 2)
    return jnp.where(oh, val, arr)


def take_cell(arr, slot, j):
    """arr[II, JJ, slot, j] as one gather batched over the cell grid and
    arr's trailing axes.  arr: [H,W,S,N,*T], slot/j: [H,W] (in range)
    -> [H,W,*T].

    Every slice is a single element, so the gather asks no layout of
    arr: on a TPU it reads the leaf in the loop carry's own layout
    (a slice along a lane-mapped axis would have XLA relayout the leaf).
    """
    nt = arr.ndim - 4
    at = jnp.stack([slot, j], axis=-1)                           # [H,W,2]
    at = jnp.broadcast_to(at.reshape(at.shape[:2] + (1,) * nt + (2,)),
                          arr.shape[:2] + arr.shape[4:] + (2,))
    dn = jax.lax.GatherDimensionNumbers(
        offset_dims=(), collapsed_slice_dims=(2, 3), start_index_map=(2, 3),
        operand_batching_dims=(0, 1) + tuple(range(4, arr.ndim)),
        start_indices_batching_dims=tuple(range(2 + nt)))
    return jax.lax.gather(arr, at, dn, (1,) * arr.ndim,
                          mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)


# --------------------------------------------------------------------------
# EXEC-A: staging — the active action emits its next message (1 per cycle)
# --------------------------------------------------------------------------

def staging_stage(cfg: EngineConfig, app: DiffusionApp, st: MachineState,
                  rows, cols):
    H, W, S, E = cfg.height, cfg.width, cfg.slots, cfg.edge_cap
    QB, WM = cfg.qbatch, cfg.msg_words
    # app-like message builder: classic scalar payload at qbatch == 1
    # (bit-exact with the pre-mq trace), the full [..., QB] query-vector
    # payload otherwise (DESIGN §10); wm pads non-app records to width
    if QB == 1:
        qmsg = lambda op_, dst_, val: make_msg(op_, dst_, f2i(val))
        wm = lambda m_: m_
    else:
        qmsg = lambda op_, dst_, val: make_qmsg(op_, dst_, f2i(val))
        wm = lambda m_: pad_msg(m_, WM)
    active = st.cvalid & (st.cphase >= 1) & (st.cphase <= st.cT)

    op = st.cmsg[..., 0]
    dst = st.cmsg[..., 1]
    slot = dst % S
    k = st.cphase - 1  # emission index
    cellid = rows * W + cols

    is_app = op == OP_APP
    if cfg.faults is not None:
        # an active OP_REPAIR emits exactly like OP_APP (edge diffusion,
        # sibling broadcast, ghost forward) — only the ghost forward
        # keeps the OP_REPAIR opcode so the *whole* chain re-diffuses
        # its edge shard even where the relax changed nothing (§9)
        is_rp = op == OP_REPAIR
        is_app = is_app | is_rp
    is_sf = op == OP_SET_FUTURE
    is_rf = op == OP_RHIZOME_FWD
    is_appl = is_app | is_rf       # app-like: edge diffusion + ghost forward

    # ---- emission for OP_APP / OP_RHIZOME_FWD: (rf only) deferred-insert
    #      drains, per-edge diffusion, (app only) sibling-rhizome
    #      broadcasts, then ghost forward ----
    kd = k - st.cdrain             # emission index past the drains (rf)
    ne = sel(st.nedges, slot)
    ek = jnp.clip(kd, 0, E - 1)
    e_dst = take_cell(st.edst, slot, ek)
    e_w = take_cell(st.ew, slot, ek)
    app_edge_msg = qmsg(OP_APP, e_dst, app.edge_value(st.cemit, e_w))
    gs = sel(st.gstate, slot)
    ga = sel(st.gaddr, slot)
    fwd_op = OP_APP if cfg.faults is None else \
        jnp.where(is_rp, OP_REPAIR, OP_APP)
    app_fwd_msg = qmsg(fwd_op, ga, st.cemit)
    # sibling broadcast window [ne, ne + n_bcast) — canonical roots of
    # multi-root vertices only (phase0 accounted for it in cT)
    rss = sel(st.rstate, slot)
    n_bcast = jnp.where(is_app & (slot < cfg.root_slots) & (rss == G_SET),
                        cfg.rhizome_cap - 1, 0)
    v_self = slot * cfg.n_cells + cellid           # vid owning a root slot
    sib = jnp.clip(kd - ne + 1, 1, cfg.rhizome_cap - 1 if cfg.rhizome_cap > 1
                   else 1)
    bc_msg = qmsg(OP_RHIZOME_FWD, rhizome_addr(cfg, v_self, sib), st.cemit)
    is_bcast = is_app & (kd >= ne) & (kd < ne + n_bcast)
    appl_is_fwd = is_appl & (kd >= ne + n_bcast) & (k >= st.cdrain)

    # ---- emission for OP_SET_FUTURE: retarget head of the future queue,
    #      then (last) the coalesced deferred app-forward, if any ----
    fqn_cur = sel(st.fq_n, slot)
    fqh_cur = sel(st.fq_head, slot)
    fq_e = take_cell(st.fq, slot, fqh_cur % cfg.futq_cap)    # [H,W,3]
    sf_is_ins = fq_e[..., 0] == OP_INSERT_EDGE
    if QB == 1:
        sf_fq_app = make_msg(OP_APP, ga, fq_e[..., 1])
    else:
        # deferred-queue entries carry one value word; the remaining
        # query slots ride as the app's neutral element (no-op relaxes)
        qn = jnp.broadcast_to(
            f2i(neutral_vec(app.init_val))[1:], (H, W, QB - 1))
        sf_fq_app = make_qmsg(OP_APP, ga,
                              jnp.concatenate([fq_e[..., 1:2], qn], axis=-1))
    # (with tail_insert a root's deferred inserts go to its chain's tail,
    # which phase 0 has set to the ghost this set-future names)
    ins_tgt = (jnp.where(slot < cfg.primary_slots, sel(st.gtail, slot), ga)
               if cfg.tail_insert else ga)
    sf_fq_msg = jnp.where(
        sf_is_ins[..., None],
        wm(make_msg(OP_INSERT_EDGE, ins_tgt, fq_e[..., 1], fq_e[..., 2])),
        sf_fq_app)
    sf_from_fq = is_sf & (fqn_cur > 0)
    sf_from_fwd = is_sf & (fqn_cur == 0)   # the coalesced forward
    fwd_here = sel(st.fwd_val, slot)
    sf_msg = jnp.where(sf_from_fq[..., None], sf_fq_msg,
                       qmsg(OP_APP, ga, fwd_here))
    if cfg.tail_insert:
        # no forward pending after the drains: the root's link of its new
        # tail behind the old, which phase 0 left in cout (DESIGN §4.6)
        fwdp = sel(st.fwd_pending, slot)
        sf_msg = jnp.where((sf_from_fwd & ~fwdp)[..., None], st.cout, sf_msg)
        sf_from_fwd = sf_from_fwd & fwdp

    # ---- rf activation drain: re-inject a deferred insert at this (now
    #      active) rhizome root — it is local by construction ----
    rf_drain = is_rf & (k < st.cdrain)
    drain_msg = wm(make_msg(OP_INSERT_EDGE, dst, fq_e[..., 1], fq_e[..., 2]))

    appl_msg = jnp.where(rf_drain[..., None], drain_msg,
                         jnp.where(appl_is_fwd[..., None], app_fwd_msg,
                                   jnp.where(is_bcast[..., None], bc_msg,
                                             app_edge_msg)))
    emis = jnp.where(is_appl[..., None], appl_msg,
                     jnp.where(is_sf[..., None], sf_msg, st.cout))
    if cfg.faults is not None:
        # staging is the single chokepoint every compute-emitted message
        # passes through (phase-0's cout rides the is_sf/is_appl=False
        # branch above), so sealing here + at the IO injector covers the
        # whole network (§9); park/rotate/hop paths copy words verbatim
        emis = seal_msg(emis)

    # ---- app ghost-forward onto a *pending* future: coalesce into the
    #      per-slot monotone forward register (never stalls — the future
    #      LCO merges dependent continuations, DESIGN §4.4) ----
    to_reg = active & appl_is_fwd & (gs == G_PENDING)
    ohreg = _oh(slot, S, to_reg)
    # the register coalesces with the app's own meet (min for the bundled
    # min-monotone apps — the pre-mq jnp.minimum — max for widest-path)
    if QB == 1:
        fwd_val = jnp.where(ohreg,
                            app.fwd_merge(st.fwd_val, st.cemit[..., None]),
                            st.fwd_val)
    else:
        fwd_val = jnp.where(ohreg[..., None],
                            app.fwd_merge(st.fwd_val,
                                          st.cemit[..., None, :]),
                            st.fwd_val)
    fwd_pending = st.fwd_pending | ohreg

    tb = yx_target_buffer(cfg, emis[..., 1] // S, rows, cols)

    # ---- try to push (network or local queue) ----
    push_active = active & ~to_reg
    # local delivery uses the reserved slots -> never self-deadlocks;
    # channel pushes enter the emission's virtual lane (escape lane 0
    # for protocol messages, destination-hashed data lane otherwise)
    aq, aq_n, ch, ch_n, ok_push = deliver(
        cfg, st.aq, st.aq_n, st.aq_head, st.ch, st.ch_n, st.ch_head,
        emis, tb, msg_lane(cfg, emis[..., 0], emis[..., 1]), push_active,
        rings.ring_free(st.aq_n, cfg.queue_cap))
    ok_total = to_reg | ok_push  # register writes always succeed
    parked = jnp.zeros_like(ok_push)
    pk, pk_n = st.pk, st.pk_n
    if cfg.lanes > 1:
        # transit parking (DESIGN §7): a remote emission whose channel
        # lane is full is stored into the cell's park buffer instead of
        # wedging the pipeline — the cell keeps consuming (the
        # consumption guarantee that, with the escape lane, makes the
        # §4.2 protocol live).  The park buffer is deliberately a
        # SEPARATE ring: in-transit messages must never occupy action-
        # queue space, or they would hold the queue above the admission
        # thresholds and starve the very deliveries that drain them.
        # routing.park_stage re-injects parked messages each cycle.  If
        # the park buffer is full the action simply stays active (the
        # pre-lane wormhole stall — lossless fallback).
        parked = (push_active & ~ok_push & (tb != TB_AQ_SELF)
                  & rings.ring_free(pk_n, cfg.park_capacity))
        pk, pk_n = rings.ring_push(pk, pk_n, st.pk_head, emis, parked)
        ok_total = ok_total | parked

    # ---- SET_FUTURE / rf-drain bookkeeping on successful stages ----
    fq_pop = ok_total & (sf_from_fq | rf_drain)
    n2, h2 = rings.ring_pop(fqn_cur, fqh_cur, cfg.futq_cap, fq_pop)
    fq_n = put(st.fq_n, slot, n2, fq_pop)
    fq_head = put(st.fq_head, slot, h2, fq_pop)
    sf_clear = ok_total & sf_from_fwd
    fwd_val = put(fwd_val, slot, neutral_vec(app.fwd_neutral), sf_clear)
    fwd_pending = fwd_pending & ~_oh(slot, S, sf_clear)

    # ---- advance / retire ----
    new_phase = st.cphase + ok_total.astype(jnp.int32)
    done = active & ok_total & (new_phase > st.cT)
    cvalid = st.cvalid & ~done
    stall = active & ~ok_total

    st = st._replace(
        aq=aq, aq_n=aq_n, ch=ch, ch_n=ch_n, pk=pk, pk_n=pk_n,
        fq_n=fq_n, fq_head=fq_head,
        fwd_val=fwd_val, fwd_pending=fwd_pending,
        cphase=new_phase, cvalid=cvalid,
        stat_exec=st.stat_exec + jnp.sum(done.astype(jnp.int32)),
        stat_stall=st.stat_stall
        + jnp.sum(stall.astype(jnp.int32))
        + jnp.sum(parked.astype(jnp.int32)))
    if cfg.telemetry:
        i32 = lambda m: m.astype(jnp.int32)
        tm = st.tm_cell
        tm = tm.at[..., TM_STAGE].add(i32(active & ok_total))
        tm = tm.at[..., TM_STALL].add(i32(stall))
        tm = tm.at[..., TM_PARK].add(i32(parked))
        tm = tm.at[..., TM_BCAST].add(i32(push_active & ok_total & is_bcast))
        st = st._replace(tm_cell=tm)
    return st, active


# --------------------------------------------------------------------------
# EXEC-B: pop + phase 0 (the action's computing instruction)
# --------------------------------------------------------------------------

def phase0_stage(cfg: EngineConfig, app: DiffusionApp, st: MachineState,
                 rows, cols, busy_at_start):
    H, W, S, E = cfg.height, cfg.width, cfg.slots, cfg.edge_cap
    FQ, Q = cfg.futq_cap, cfg.queue_cap
    QB, WM = cfg.qbatch, cfg.msg_words
    wm = (lambda m_: m_) if QB == 1 else (lambda m_: pad_msg(m_, WM))
    cellid = rows * W + cols

    idle = ~busy_at_start
    has = idle & (st.aq_n > 0)
    m = rings.ring_peek(st.aq, st.aq_head)  # [H,W,MSG]
    op = jnp.where(has, m[..., 0], 0)
    if cfg.faults is not None:
        # seal validation (DESIGN §9): an app/repair flit whose XOR seal
        # no longer matches was corrupted in transit — discard it as a
        # counted no-op rather than relax with a poisoned value (a
        # corrupted-low level could never be un-relaxed from a monotone
        # fixpoint).  Protocol traffic is never corrupted by a FaultPlan
        # so restricting the check keeps legacy in-state messages valid.
        from repro.resilience.faults import FLT_CORRUPT, is_droppable
        bad = has & is_droppable(op) & (msg_seal(m) != m[..., 4])
        op = jnp.where(bad, 0, op)
    dst, a0, a1 = m[..., 1], m[..., 2], m[..., 3]
    slot = dst % S

    # [H,W,VN]: the apps relax a value vector; at qbatch == 1 the
    # stored leaf is [H,W,S] (state.vals_index) and VN == 1
    vals_s = sel(st.vals, slot)
    if QB == 1:
        vals_s = vals_s[..., None]
    ne = sel(st.nedges, slot)
    gs = sel(st.gstate, slot)
    fqn = sel(st.fq_n, slot)
    rs = sel(st.rstate, slot)
    on_s = sel(st.rhz_on, slot)

    is_ins = op == OP_INSERT_EDGE
    is_app = op == OP_APP
    is_alc = op == OP_ALLOC
    is_sf = op == OP_SET_FUTURE
    is_rf = op == OP_RHIZOME_FWD
    is_lr = op == OP_LINK_RHIZOME
    # recovery-path relax (DESIGN §9): like OP_APP but *forces* the
    # re-diffusion emissions even when the relax did not change the
    # value — rebuilding downstream state lost to dropped flits
    is_rp = (op == OP_REPAIR) if cfg.faults is not None else None

    # secondary rhizome slots are statically reserved but start inactive;
    # an insert reaching one before its link-ack must defer (DESIGN §4.5)
    in_sec = (slot >= cfg.root_slots) & (slot < cfg.primary_slots)
    inactive = in_sec & ~on_s
    ti = cfg.tail_insert
    if ti:
        # a root's view of its ghost chain's last node (DESIGN §4.6)
        is_root = slot < cfg.primary_slots
        tc = sel(st.tcnt, slot)
        gt = sel(st.gtail, slot)

    # ---------------- INSERT-EDGE paths (Listing 6) ----------------
    room = ne < E
    p_room = is_ins & ~inactive & room
    p_fwd = is_ins & ~inactive & ~room & (gs == G_SET)
    p_defer = is_ins & ~inactive & ~room & (gs == G_PENDING)
    p_null = is_ins & ~inactive & ~room & (gs == G_NULL)
    # rhizome growth: first insert at an inactive root requests the link,
    # later ones just defer on the same future queue (Fig. 4 machinery)
    p_rlink = is_ins & inactive & (rs == G_NULL)
    p_rdef = is_ins & inactive & (rs == G_PENDING)
    defers = p_defer | p_rlink | p_rdef
    if ti:
        # a full root sends the insert to its tail while the tail has
        # room, asks for a new tail once it is full, and defers on its
        # future queue while that request is out
        p_tnull = p_fwd & is_root & (tc == E)
        p_tdef = p_fwd & is_root & (tc > E)
        p_fwd = p_fwd & ~(p_tnull | p_tdef)
        defers = defers | p_tnull | p_tdef

    # the only infeasible phase-0: deferred insert with a full future
    # queue.  The head is ROTATED to the queue tail (costs this cell's
    # cycle) — the paper's runtime "schedules other tasks", so a blocked
    # action never wedges the FIFO in front of the set-future it waits on.
    feasible = ~(defers & (fqn >= FQ))
    pop = has & feasible
    rotate = has & ~feasible
    p_room &= pop; p_fwd &= pop; p_defer &= pop; p_null &= pop
    p_rlink &= pop; p_rdef &= pop
    if ti:
        p_tnull &= pop; p_tdef &= pop
    is_app &= pop; is_alc &= pop; is_sf &= pop; is_rf &= pop; is_lr &= pop
    if is_rp is not None:
        is_rp &= pop

    # -- room: insert the edge into this RPVO node
    eidx = jnp.minimum(ne, E - 1)
    ohSE = (_oh(slot, S, p_room)[..., None]
            & _oh(eidx, E)[..., None, :])                    # [H,W,S,E]
    edst = jnp.where(ohSE, a0[..., None, None], st.edst)
    ew = jnp.where(ohSE, i2f(a1)[..., None, None], st.ew)
    nedges = st.nedges + _oh(slot, S, p_room).astype(jnp.int32)
    prop = app.propagate_on_insert(vals_s)
    ins_T = (p_room & prop).astype(jnp.int32)
    if QB == 1:
        ins_out = make_msg(OP_APP, a0,
                           f2i(app.edge_value(vals_s[..., 0], i2f(a1))))
    else:
        # the insert-propagate relax carries the whole query vector: one
        # wave serves every tenant (DESIGN §10)
        ins_out = make_qmsg(OP_APP, a0,
                            f2i(app.edge_value(vals_s, i2f(a1))))

    # -- fwd: recursively propagate the insert to the ghost (Listing 6 l.29)
    ga_cur = sel(st.gaddr, slot)
    fwd_tgt = jnp.where(is_root, gt, ga_cur) if ti else ga_cur
    fwd_out = wm(make_msg(OP_INSERT_EDGE, fwd_tgt, a0, a1))

    # -- defer: enqueue the insert on the pending future (Fig. 4 step 3)
    # (rhizome-pending slots reuse the same queue: Fig. 4 step 3 again)
    push_mask = p_defer | p_null | p_rlink | p_rdef
    if ti:
        push_mask = push_mask | p_tnull | p_tdef
    fqh = sel(st.fq_head, slot)
    tailq = (fqh + fqn) % FQ
    ohq = (_oh(slot, S, push_mask)[..., None]
           & _oh(tailq, FQ)[..., None, :])                   # [H,W,S,FQ]
    entry = jnp.stack([jnp.full((H, W), OP_INSERT_EDGE, jnp.int32), a0, a1],
                      axis=-1)                               # [H,W,3]
    fq = jnp.where(ohq[..., None], entry[..., None, None, :], st.fq)
    fq_n = st.fq_n + _oh(slot, S, push_mask).astype(jnp.int32)

    # -- null: future -> pending, send allocate with continuation (Fig. 3)
    gstate = put(st.gstate, slot, G_PENDING, p_null)
    tgt_cell = choose_alloc_cell(cfg, rows, cols, st.arot)
    # (a root's request for a new tail is the same allocate, answered by
    # the same set-future)
    asks = (p_null | p_tnull) if ti else p_null
    arot = st.arot + asks.astype(jnp.int32)
    null_out = make_msg(OP_ALLOC, tgt_cell * S, dst, f2i(vals_s[..., 0]))
    if QB > 1:
        # OP_ALLOC carries the requester's full value vector: word 3 is
        # slot 0 (as ever), the extension words are slots 1.. (§10)
        null_out = jnp.concatenate([null_out, f2i(vals_s[..., 1:])], axis=-1)

    # -- rlink: mark pending, request activation at the canonical root
    rstate = put(st.rstate, slot, G_PENDING, p_rlink)
    owner = rhizome_owner_vid(cfg, cellid, slot)
    owner_root = (owner % cfg.n_cells) * S + owner // cfg.n_cells
    rlink_out = wm(make_msg(OP_LINK_RHIZOME, owner_root, cellid * S + slot))

    # ---------------- APP / RHIZOME-FWD relax (Listing 5) ----------------
    relaxing = is_app | is_rf
    app_like = is_app
    if is_rp is not None:
        relaxing = relaxing | is_rp
        app_like = is_app | is_rp
    if QB == 1:
        new_vals, changed = app.relax(vals_s, i2f(a0))
        changed = changed & relaxing
    else:
        # vector relax over the query axis (DESIGN §10): the incoming
        # payload spans all query slots; the qsel bitmask (word 3, 0 =
        # all) masks de-selected slots to their app's neutral element so
        # an admission re-seed relaxes exactly one tenant
        inc = i2f(msg_qvals(m, QB))                       # [H,W,QB]
        inc = jnp.where(qsel_mask(a1, QB), inc, neutral_vec(app.init_val))
        new_vals, changed_q = app.relax(vals_s, inc)
        changed_q = changed_q & relaxing[..., None]       # [H,W,QB]
        changed = jnp.any(changed_q, axis=-1)
    vals = put(st.vals, slot, new_vals[..., 0] if QB == 1 else new_vals,
               relaxing)
    # a changed relax at a canonical root of a multi-root vertex also
    # broadcasts to the R-1 sibling rhizomes — in parallel, replacing the
    # serial forward walk of the chain design (DESIGN §4.5).  The root
    # learns it is multi-root when it handles the first OP_LINK_RHIZOME.
    n_bcast = jnp.where(app_like & (slot < cfg.root_slots) & (rs == G_SET),
                        cfg.rhizome_cap - 1, 0)
    forced = changed if is_rp is None else changed | is_rp
    app_T = jnp.where(forced,
                      ne + n_bcast + (gs != G_NULL).astype(jnp.int32), 0)
    cemit_new = new_vals[..., 0] if QB == 1 else new_vals

    # -- rhizome-fwd extras: activate a pending/inactive sibling root and
    #    drain its deferred inserts back onto the local action queue.  The
    #    gstate gate keeps ghost-protocol deferrals (G_PENDING) parked for
    #    their set-future instead of bouncing them through the queue.
    rf_act = is_rf & in_sec & ~on_s
    rhz_on = jnp.where(_oh(slot, S, rf_act), True, st.rhz_on)
    rstate = put(rstate, slot, G_SET, rf_act)
    # the ne == 0 gate makes the §4.2 local-emission bound locally
    # provable: a draining rf emits <= futq_cap (<= aq_reserve) and a
    # diffusing rf emits <= edge_cap + 1, never both.  (Protocol-wise a
    # slot with fq entries is either ghost-pending or pre-activation with
    # zero edges, so the gate never strands an entry.)
    drain_n = jnp.where(is_rf & (gs != G_PENDING) & (ne == 0), fqn, 0)
    rf_T = drain_n + jnp.where(is_rf & changed,
                               ne + (gs != G_NULL).astype(jnp.int32), 0)
    app_T = jnp.where(is_rf, 0, app_T)

    # ---------------- LINK-RHIZOME (canonical-root handler) ----------
    # remember the vertex is multi-root; ack with the current value (the
    # ack is itself an OP_RHIZOME_FWD, so it also syncs the new sibling)
    rstate = put(rstate, slot, G_SET, is_lr)
    if QB == 1:
        lr_out = make_msg(OP_RHIZOME_FWD, a0, f2i(vals_s[..., 0]))
    else:
        lr_out = make_qmsg(OP_RHIZOME_FWD, a0, f2i(vals_s))

    # ---------------- ALLOC (system action) ----------------
    alc_room = is_alc & (st.nfree < S)
    alc_full = is_alc & ~(st.nfree < S)
    g_new = st.nfree
    if QB == 1:
        gseed = i2f(a1)
    else:
        # the allocation request carried the requester's whole value
        # vector (word 3 + extension words), so the ghost starts synced
        gseed = i2f(jnp.concatenate([a1[..., None], m[..., MSG_WORDS:]],
                                    axis=-1))
    vals = put(vals, g_new, gseed, alc_room)
    nedges = put(nedges, g_new, 0, alc_room)
    gaddr0 = put(st.gaddr, g_new, -1, alc_room)
    gstate = put(gstate, g_new, G_NULL, alc_room)
    fq_n = put(fq_n, g_new, 0, alc_room)
    fq_head = put(st.fq_head, g_new, 0, alc_room)
    fwd_val = put(st.fwd_val, g_new, neutral_vec(app.fwd_neutral), alc_room)
    fwd_pending = st.fwd_pending & ~_oh(g_new, S, alc_room)
    new_addr = cellid * S + st.nfree
    nfree = st.nfree + alc_room.astype(jnp.int32)
    alc_ok_out = wm(make_msg(OP_SET_FUTURE, a0, new_addr))
    nxt_cell = (cellid + 1) % cfg.n_cells
    alc_fwd_out = make_msg(OP_ALLOC, nxt_cell * S, a0, a1)
    if QB > 1:
        alc_fwd_out = jnp.concatenate([alc_fwd_out, m[..., MSG_WORDS:]],
                                      axis=-1)

    # ---------------- SET-FUTURE (continuation return, Fig. 3/4) ----------
    sf_T = jnp.where(is_sf,
                     fqn + sel(st.fwd_pending, slot).astype(jnp.int32), 0)
    if ti:
        # at a root: the answer to a request for a new tail (the chain
        # exists, gstate is set) keeps gaddr and is linked behind the old
        # tail by one more emission, cout; the deferred inserts drain to
        # the new tail, which then holds them (DESIGN §4.6)
        sf_root = is_sf & is_root
        sf_link = sf_root & (gs == G_SET)
        gaddr = put(gaddr0, slot, a0, is_sf & ~sf_link)
        gtail = put(st.gtail, slot, a0, sf_root)
        tcnt = put(st.tcnt, slot, tc + 1, p_fwd & is_root)
        tcnt = put(tcnt, slot, tail_pending(cfg), p_tnull)
        tcnt = put(tcnt, slot, fqn, sf_root)
        link_out = wm(make_msg(OP_SET_FUTURE, gt, a0))
        # at the old tail, that link: forward the tail's own value to the
        # new ghost through the coalesced-forward register, so that no
        # relax that reached the old tail before its link is lost
        linked = is_sf & ~is_root & (gs == G_NULL)
        fwd_val = put(fwd_val, slot,
                      vals_s[..., 0] if QB == 1 else vals_s, linked)
        fwd_pending = fwd_pending | _oh(slot, S, linked)
        sf_T = sf_T + (sf_link | linked).astype(jnp.int32)
    else:
        gaddr = put(gaddr0, slot, a0, is_sf)
    gstate = put(gstate, slot, G_SET, is_sf)

    # ---------------- combine: T, cout, registers, queue pop --------------
    T = (ins_T
         + jnp.where(p_fwd | asks | p_rlink | alc_room | alc_full | is_lr,
                     1, 0)
         + app_T + sf_T + rf_T)
    cout = jnp.where(p_room[..., None], ins_out,
            jnp.where(p_fwd[..., None], fwd_out,
             jnp.where(asks[..., None], null_out,
              jnp.where(p_rlink[..., None], rlink_out,
               jnp.where(is_lr[..., None], lr_out,
                jnp.where(alc_room[..., None], alc_ok_out,
                 jnp.where(alc_full[..., None], alc_fwd_out, st.cout)))))))
    if ti:
        cout = jnp.where(sf_link[..., None], link_out, cout)

    # pop (feasible) or rotate-to-tail (infeasible): head always advances
    move = pop | rotate
    tail = (st.aq_head + st.aq_n) % Q
    ohT = _oh(tail, Q, rotate)                                # [H,W,Q]
    aq = jnp.where(ohT[..., None], m[..., None, :], st.aq)
    aq_n2 = st.aq_n - pop.astype(jnp.int32)
    aq_h2 = (st.aq_head + move.astype(jnp.int32)) % Q
    done0 = pop & (T == 0)   # single-cycle action
    cvalid = st.cvalid | (pop & (T > 0))
    cmsg = jnp.where(pop[..., None], m, st.cmsg)
    cphase = jnp.where(pop, 1, st.cphase)
    cT = jnp.where(pop, T, st.cT)
    cemit = jnp.where(relaxing if QB == 1 else relaxing[..., None],
                      cemit_new, st.cemit)
    cdrain = jnp.where(pop, jnp.where(is_rf, drain_n, 0), st.cdrain)

    st = st._replace(
        vals=vals, nedges=nedges, edst=edst, ew=ew, gaddr=gaddr,
        gstate=gstate, rhz_on=rhz_on, rstate=rstate, nfree=nfree,
        fq=fq, fq_n=fq_n, fq_head=fq_head,
        fwd_val=fwd_val, fwd_pending=fwd_pending,
        aq=aq, aq_n=aq_n2, aq_head=aq_h2, arot=arot,
        cmsg=cmsg, cvalid=cvalid, cphase=cphase, cT=cT, cemit=cemit,
        cout=cout, cdrain=cdrain,
        **(dict(gtail=gtail, tcnt=tcnt) if ti else {}),
        stat_exec=st.stat_exec + jnp.sum(done0.astype(jnp.int32)),
        stat_allocs=st.stat_allocs + jnp.sum(alc_room.astype(jnp.int32)),
        stat_stall=st.stat_stall + jnp.sum(rotate.astype(jnp.int32)))
    if QB > 1:
        # per-query activity counters (repro.mq, DESIGN §10): a query
        # slot that relaxed nowhere this cycle is one cycle closer to
        # its own quiescence — the session layer diffs qchg across
        # increments and reads qlast as the slot's settle cycle
        dq = jnp.sum(changed_q.astype(jnp.int32), axis=(0, 1))
        st = st._replace(qchg=st.qchg + dq,
                         qlast=jnp.where(dq > 0, st.cycle, st.qlast))
    if cfg.rhizome_cap > 1:
        # the protocol's own work, apart from phase0's other actions: link
        # requests popped here (each emits one OP_LINK_RHIZOME) and the
        # OP_RHIZOME_FWD actions executed
        st = st._replace(stat_rhz=st.stat_rhz
                         .at[RZ_LINK].add(jnp.sum(p_rlink.astype(jnp.int32)))
                         .at[RZ_FWD].add(jnp.sum(is_rf.astype(jnp.int32))))
    if cfg.faults is not None:
        st = st._replace(flt=st.flt.at[FLT_CORRUPT].add(
            jnp.sum(bad.astype(jnp.int32))))
    if cfg.telemetry:
        tm = st.tm_cell
        tm = tm.at[..., TM_EXEC].add(pop.astype(jnp.int32))
        tm = tm.at[..., TM_ALLOC].add(alc_room.astype(jnp.int32))
        tm = tm.at[..., TM_STALL].add(rotate.astype(jnp.int32))
        st = st._replace(tm_cell=tm)
    return st, pop
