"""MQSession — serve Q concurrent queries over one evolving graph.

The session wraps a :class:`StreamingEngine` built from a qbatch=Q
composite app (``mq.app.batch_app``) and adds the tenant lifecycle
(DESIGN §10):

* **admit** a query mid-stream into a free slot: reset ONLY that slot's
  value plane to its app's neutral element (the live graph structure is
  shared and untouched) and inject a qsel-masked ``OP_APP`` seed at the
  source's canonical root — one message, relaxing exactly one tenant.
  Label-flood queries (CC) instead host-write every vertex's label and
  must be admitted before any edges stream in (existing edges never
  re-trigger; inserts do the propagation from then on).
* **track quiescence per query** from the ``qchg`` per-slot relax
  counters the execute stage accumulates: a slot whose counter stayed
  zero across an increment has settled, and ``qlast`` holds the exact
  cycle of its last relax (its time-to-quiescence end point).
* **retire / recycle** settled slots: readback with the slot app's own
  root combine, then the slot (with a bumped generation) is free for the
  next tenant — admitting a different app rebuilds the composite, which
  is just a jit recompile (the app is a static argument).

Admission happens only at increment boundaries, where the machine is
quiescent: no messages are in flight, so a recycled slot can never
observe a stale payload from its previous generation.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core.alloc import rhizome_rcs
from repro.core.apps import APPS, DiffusionApp
from repro.core.config import EngineConfig
from repro.core.engine import StreamingEngine
from repro.core.msg import MSG_WORDS, OP_APP
from repro.core.state import root_addr, vals_index
from repro.mq.app import batch_app
from repro.obs.spans import span

# default seed value per app family: the value a source vertex starts
# from (BFS/SSSP distance 0; widest bottleneck +INF; reliable prob 1)
DEFAULT_SEEDS = {"bfs": 0.0, "sssp": 0.0, "widest": 1e9, "reliable": 1.0}

# label-flood apps: admission = host label write at stream start, no
# seed message (every vertex is its own source)
LABEL_APPS = ("cc",)


@dataclasses.dataclass
class QuerySlot:
    """One tenant: app id + source + generation (ISSUE §10 slot tuple)."""
    app: DiffusionApp | None = None
    source: int = -1
    generation: int = 0
    state: str = "free"          # free | active | settled
    admit_cycle: int = 0
    settle_cycle: int | None = None   # qlast at first all-quiet boundary
    increments: int = 0

    @property
    def latency_cycles(self) -> int | None:
        if self.settle_cycle is None:
            return None
        return self.settle_cycle - self.admit_cycle


class MQSession:
    """Q-batched serving session over one StreamingEngine."""

    def __init__(self, cfg: EngineConfig, qbatch: int,
                 apps: "list[str] | None" = None):
        # slot apps are jit-static; start every slot on BFS (the cheapest
        # composite) — admit() rebuilds when a tenant needs another app
        names = list(apps) if apps else ["bfs"] * qbatch
        assert len(names) == qbatch
        self.composite = batch_app(names)
        self.eng = StreamingEngine(cfg, self.composite)
        self.slots = [QuerySlot() for _ in range(qbatch)]
        self.edges_seen = 0

    @property
    def qbatch(self) -> int:
        return self.eng.cfg.qbatch

    @property
    def slot_apps(self) -> tuple:
        return (self.composite.slot_apps if self.composite.qbatch > 1
                else (self.composite,))

    # ---------------- admission ----------------

    def free_slots(self) -> "list[int]":
        return [q for q, s in enumerate(self.slots) if s.state == "free"]

    def admit(self, app: str | DiffusionApp, source: int,
              slot: int | None = None, seed: float | None = None) -> int:
        """Admit a query into a free slot; returns the slot index.

        Single-source apps admit at any increment boundary.  Label-flood
        apps (CC) only before the first edge streams in.
        """
        a = APPS[app] if isinstance(app, str) else app
        if slot is None:
            free = self.free_slots()
            if not free:
                raise RuntimeError("no free query slot (retire one first)")
            slot = free[0]
        s = self.slots[slot]
        assert s.state == "free", f"slot {slot} is {s.state}"
        if a.name in LABEL_APPS and self.edges_seen:
            raise ValueError(
                f"label-flood app {a.name!r} must be admitted before the "
                "stream starts (existing edges never re-trigger)")
        if self.slot_apps[slot].name != a.name:
            self._rebuild(slot, a)
        self._reset_slot_plane(slot)
        cycle = int(self.eng.state.cycle)
        if a.name in LABEL_APPS:
            self._write_labels(slot)
        else:
            self._inject_seed(
                slot, source,
                DEFAULT_SEEDS[a.name] if seed is None else seed)
        self.slots[slot] = QuerySlot(app=a, source=source,
                                     generation=s.generation + 1,
                                     state="active", admit_cycle=cycle)
        return slot

    def _rebuild(self, slot: int, a: DiffusionApp):
        names = [sa.name for sa in self.slot_apps]
        names[slot] = a.name
        self.composite = batch_app(names)
        self.eng.app = self.composite
        # n_vals / qbatch are unchanged, so the machine state fits as-is;
        # the next device call recompiles against the new static app

    def _reset_slot_plane(self, slot: int):
        """Host-reset slot ``slot``'s value plane to its app's neutral —
        graph structure (edges, ghosts, rhizomes) is untouched."""
        eng, q = self.eng, slot
        init = jnp.float32(np.float32(
            self.composite.init_val[q] if self.composite.qbatch > 1
            else self.composite.init_val))
        neutral = jnp.float32(np.float32(
            self.composite.fwd_neutral[q] if self.composite.qbatch > 1
            else self.composite.fwd_neutral))
        st = eng.state
        if self.qbatch == 1:
            st = st._replace(vals=st.vals.at[...].set(init),
                             fwd_val=st.fwd_val.at[...].set(neutral))
        else:
            st = st._replace(
                vals=st.vals.at[..., q].set(init),
                fwd_val=st.fwd_val.at[..., q].set(neutral),
                qchg=st.qchg.at[q].set(0),
                qlast=st.qlast.at[q].set(st.cycle))
        eng.state = st

    def _write_labels(self, slot: int):
        """CC-style admission: every vertex becomes its own source."""
        eng, cfg = self.eng, self.eng.cfg
        vids = np.arange(cfg.n_vertices, dtype=np.int64)[None, :]
        ks = np.arange(cfg.rhizome_cap, dtype=np.int64)[:, None]
        r, c, s = rhizome_rcs(cfg, vids, ks)
        labels = np.broadcast_to(vids.astype(np.float32), r.shape)
        eng.state = eng.state._replace(
            vals=eng.state.vals.at[vals_index(cfg, r, c, s, q=slot)]
            .set(jnp.asarray(labels)))

    def _inject_seed(self, slot: int, source: int, seed: float):
        """Push one qsel-masked OP_APP onto the action queue of the
        source's canonical-root cell (the boundary is quiescent, so the
        queue has room and no in-flight message can reorder with it)."""
        eng, cfg = self.eng, self.eng.cfg
        addr = int(root_addr(cfg, np.int64(source)))
        cell = addr // cfg.slots
        r, c = cell // cfg.width, cell % cfg.width
        WM = cfg.msg_words
        m = np.zeros(WM, np.int32)
        m[0], m[1] = OP_APP, addr
        if self.qbatch == 1:
            m[2] = np.float32(seed).view(np.int32)
        else:
            payload = np.asarray(self.composite.init_val,
                                 np.float32).copy()
            payload[slot] = seed
            bits = payload.view(np.int32)
            m[2] = bits[0]
            m[MSG_WORDS:] = bits[1:]
            m[3] = 1 << slot          # qsel: relax tenant `slot` only
        aq = np.asarray(eng.state.aq).copy()
        aq_n = np.asarray(eng.state.aq_n).copy()
        head = np.asarray(eng.state.aq_head)
        assert aq_n[r, c] < cfg.queue_cap, "action queue full at boundary?"
        tail = (head[r, c] + aq_n[r, c]) % cfg.queue_cap
        aq[r, c, tail] = m
        aq_n[r, c] += 1
        eng.state = eng.state._replace(aq=jnp.asarray(aq),
                                       aq_n=jnp.asarray(aq_n))

    # ---------------- streaming ----------------

    def run_increment(self, edges, **kw):
        """Ingest one edge increment, run to global quiescence, then fold
        the per-slot relax counters into each tenant's lifecycle, under
        the host span ``repro.mq.fold`` (its ``inc`` is the engine's
        increment's; the ``qchg``/``qlast`` readbacks wait for the
        device)."""
        edges = np.asarray(edges, np.int32).reshape(-1, 3)
        res = self.eng.run_increment(edges, **kw)
        with span("repro.mq.fold", inc=self.eng.stream_pos - 1):
            self.edges_seen += len(edges)
            qchg = np.asarray(self.eng.state.qchg)
            qlast = np.asarray(self.eng.state.qlast)
            end_cycle = int(self.eng.state.cycle)
            for q, s in enumerate(self.slots):
                if s.state == "free":
                    continue
                s.increments += 1
                if self.qbatch == 1:
                    # no per-slot counters at qbatch == 1 (they are [1]
                    # dummies, kept un-updated for the bit-exact trace);
                    # global quiescence IS the query's quiescence, with
                    # the boundary cycle as a conservative settle point
                    changed = 1 if len(edges) else 0
                    last = end_cycle
                else:
                    changed = int(qchg[q])
                    last = int(qlast[q])
                if s.state == "active" and changed == 0:
                    s.state = "settled"
                    s.settle_cycle = last
                elif s.state == "settled" and changed > 0:
                    # the evolving graph re-activated a settled tenant;
                    # its first-settle latency is already recorded
                    s.state = "active"
        return res

    # ---------------- readback / retirement ----------------

    def values(self, slot: int, n: int | None = None) -> np.ndarray:
        """Per-query values: the slot's own plane, root-combined with the
        slot app's OWN reduce (min for min-monotone, max for widest)."""
        a = self.slot_apps[slot]
        return self.eng.values(n, val_idx=slot, combine=a.combine)

    def settled_slots(self) -> "list[int]":
        return [q for q, s in enumerate(self.slots) if s.state == "settled"]

    def retire(self, slot: int, collect_values: bool = False) -> dict:
        """Free a slot for recycling; returns the tenant's receipt."""
        s = self.slots[slot]
        assert s.state != "free", f"slot {slot} already free"
        receipt = dict(slot=slot, app=s.app.name, source=s.source,
                       generation=s.generation,
                       admit_cycle=s.admit_cycle,
                       settle_cycle=s.settle_cycle,
                       latency_cycles=s.latency_cycles,
                       increments=s.increments)
        if collect_values:
            receipt["values"] = self.values(slot)
        self.slots[slot] = QuerySlot(generation=s.generation)
        return receipt
