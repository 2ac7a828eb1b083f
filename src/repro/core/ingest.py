"""Streaming edge ingestion via IO cells (paper §2, §4 "Graph Construction").

One IO cell per chip column, attached to the row-0 cell of its column.
Every cycle each IO cell reads the next edge of its residual stream,
creates the registered ``insert-edge-action`` and sends it to its connected
Compute Cell — entering the routing fabric there (action queue if the
target vertex lives on that cell, else the proper YX outgoing channel).
Backpressure stalls the IO cell (it retries the same edge next cycle).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import rings
from repro.core.alloc import rhizome_addr
from repro.core.config import EngineConfig
from repro.core.msg import (OP_INSERT_EDGE, OP_REPAIR, make_msg, pad_msg,
                            seal_msg)
from repro.core.routing import (deliver, manhattan_hops, msg_lane,
                                yx_target_buffer)
from repro.core.state import MachineState, TM_IO, root_addr
from repro.obs.spans import span


def load_stream(cfg: EngineConfig, st: MachineState, edges: np.ndarray,
                limit: int | None = None):
    """Distribute an increment's edges round-robin over the IO cells.

    edges: int32 [m, 3] rows of (src vid, dst vid, weight bits).
    Any residue from a previous increment is preserved (appended after).

    Returns ``(state, spill)``: edges that did not fit the per-IO-cell
    residual-stream capacity are returned (in arrival order) instead of
    asserting — the engine re-loads them once the loaded prefix has been
    consumed (spill-to-next-pass residue, DESIGN §4.2).

    ``limit`` caps the number of NEW edges admitted this call (residue
    always reloads in full); the rest spill.  This is the ingest-guard
    backpressure knob (DESIGN §9): the engine lowers the limit when the
    ``tm_hiw`` action-queue hi-water mark shows the fabric saturating,
    so ingest throttles instead of wedging the machine.

    Runs under the host span ``repro.load_stream``, with the children
    ``repro.load_stream.fetch`` (the device-to-host read of the IO
    buffers, which waits for the device) and
    ``repro.load_stream.upload`` (the host-to-device copies).
    """
    IO, L = cfg.io_cells, cfg.io_stream_cap
    with span("repro.load_stream"):
        with span("repro.load_stream.fetch"):
            io_edges = np.asarray(st.io_edges)
            io_n = np.asarray(st.io_n).copy()
            io_pos = np.asarray(st.io_pos).copy()
        # compact: drop consumed prefix
        new_edges = np.zeros_like(io_edges)
        new_n = np.zeros_like(io_n)
        for i in range(IO):
            rem = io_edges[i, io_pos[i]:io_n[i]]
            new_edges[i, :len(rem)] = rem
            new_n[i] = len(rem)
        edges = np.asarray(edges, np.int32).reshape(-1, 3)
        spill = []
        admitted = 0
        for k, e in enumerate(edges):
            i = k % IO
            if new_n[i] >= L or (limit is not None and admitted >= limit):
                spill.append(e)
                continue
            new_edges[i, new_n[i]] = e
            new_n[i] += 1
            admitted += 1
        with span("repro.load_stream.upload"):
            st = st._replace(io_edges=jnp.asarray(new_edges),
                             io_n=jnp.asarray(new_n),
                             io_pos=jnp.zeros_like(st.io_pos))
    return st, (np.stack(spill) if spill
                else np.zeros((0, 3), np.int32))


def io_stage(cfg: EngineConfig, st: MachineState, rows, cols):
    """One injection attempt per IO cell per cycle (vectorized on row 0)."""
    S, Q = cfg.slots, cfg.queue_cap
    IO = cfg.io_cells  # == width
    pend = st.io_pos < st.io_n                       # [IO]
    cur = st.io_edges[jnp.arange(IO), jnp.minimum(st.io_pos, cfg.io_stream_cap - 1)]

    r0 = jnp.zeros((IO,), jnp.int32)
    c0 = jnp.arange(IO, dtype=jnp.int32)
    # Route the insert to the nearest rhizome root of the src vertex,
    # under a per-IO-cell round-robin preference (DESIGN §4.5): the
    # rotation shards a hub's inserts evenly over its co-equal roots
    # (pure nearest would collapse onto whichever root sits closest to
    # the IO row and re-serialize the hub), while the routing distance
    # overrides the rotation when another root is more than half a chip
    # diameter closer.  With rhizome_cap=1 this is exactly the canonical
    # root.  Edge destinations always name the canonical root: the
    # application diffusion relaxes there and fans out to siblings.
    R = cfg.rhizome_cap
    ks = jnp.arange(R, dtype=jnp.int32)[None, :]
    cand = rhizome_addr(cfg, cur[:, 0:1], ks)        # [IO, R]
    dist = manhattan_hops(cfg, cand // S, r0[:, None], c0[:, None])
    half_diam = max(1, (cfg.height + cfg.width - 2) // 2)
    pref = (ks - st.io_pos[:, None]) % R             # 0 = rotation favorite
    best = jnp.argmin(dist + pref * half_diam, axis=1)
    tgt = jnp.take_along_axis(cand, best[:, None], axis=1)[:, 0]
    msg = make_msg(OP_INSERT_EDGE, tgt, root_addr(cfg, cur[:, 1]), cur[:, 2])
    if cfg.qbatch > 1:
        # insert-edge payload is (dst, weight) only — the query-axis
        # extension words of a qbatch > 1 machine are dead here (§10)
        msg = pad_msg(msg, cfg.msg_words)
    if cfg.faults is not None:
        # repair-injection sentinel (DESIGN §9): a stream row with a
        # NEGATIVE dst word is not an edge but a recovery relax —
        # ``(vid, -(k+1), value_bits)`` re-injects the durable value of
        # ``vid`` at its rhizome root ``k`` as an OP_REPAIR, reusing the
        # whole IO admission/backpressure machinery for the repair pass
        rp = cur[:, 1] < 0
        k_rp = -cur[:, 1] - 1
        rp_tgt = rhizome_addr(cfg, cur[:, 0], k_rp)
        tgt = jnp.where(rp, rp_tgt, tgt)
        msg = jnp.where(rp[:, None],
                        make_msg(OP_REPAIR, rp_tgt, cur[:, 2]), msg)
        msg = seal_msg(msg)

    tb = yx_target_buffer(cfg, tgt // S, r0, c0)     # [IO]

    # delivery on the row-0 slices (deliver is shape-polymorphic: [IO]
    # leading batch dim here, the full [H,W] grid in hop/staging); the
    # injected inserts are application traffic, so they take a
    # destination-hashed data lane and the app-level AQ reserve rule
    aq0, aqn0, ch0, chn0, accepted = deliver(
        cfg, st.aq[0], st.aq_n[0], st.aq_head[0],
        st.ch[0], st.ch_n[0], st.ch_head[0], msg, tb,
        msg_lane(cfg, msg[..., 0], msg[..., 1]), pend,
        rings.ring_free(st.aq_n[0], Q, cfg.aq_reserve + cfg.sys_reserve))
    aq = st.aq.at[0].set(aq0)
    aq_n = st.aq_n.at[0].set(aqn0)
    ch = st.ch.at[0].set(ch0)
    ch_n = st.ch_n.at[0].set(chn0)

    io_pos = st.io_pos + accepted.astype(jnp.int32)
    st = st._replace(aq=aq, aq_n=aq_n, ch=ch, ch_n=ch_n, io_pos=io_pos)
    if cfg.telemetry:
        # IO cells sit on row 0 (one per column == IO)
        st = st._replace(tm_cell=st.tm_cell.at[0, :, TM_IO]
                         .add(accepted.astype(jnp.int32)))
    return st
