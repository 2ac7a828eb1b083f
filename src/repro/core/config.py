"""Engine configuration for the AM-CCA-style message-driven machine.

The paper simulates a 32x32 chip of Compute Cells (CCs), each with local
memory (vertex slots), an action queue, and four mesh links (N/S/E/W) with
one-hop-per-cycle YX dimension-ordered routing.  All capacities below are
static so the whole machine state is a fixed-shape JAX pytree.
"""
from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    # --- chip geometry (paper: 32x32) ---
    height: int = 32
    width: int = 32

    # --- RPVO storage ---
    n_vertices: int = 1024        # logical vertices (roots, round-robin placed)
    edge_cap: int = 8             # edges per RPVO node before spilling to ghost
    ghost_slots: int = 64         # ghost slots per cell (beyond root slots)
    rhizome_cap: int = 1          # co-equal roots per vertex (DESIGN §4.5);
                                  # 1 = classic single root + serial ghost chain

    # --- queues / buffers ---
    queue_cap: int = 32           # per-cell action queue
    chan_cap: int = 8             # per-cell per-direction outgoing channel
    futq_cap: int = 8             # per-future deferred-task queue (Fig. 4)

    # --- virtual lanes (DESIGN §7) ---
    lanes: int = 1                # virtual lanes per physical channel; lane 0
                                  # is the escape lane reserved for protocol /
                                  # continuation traffic, lanes 1.. hash app
                                  # messages by destination.  1 = the classic
                                  # single-FIFO channel (bit-exact with the
                                  # pre-lane engine).
    lane_cap: int = 0             # per-lane ring capacity; 0 -> split the
                                  # physical channel: max(1, chan_cap // lanes)
    park_cap: int = 0             # per-cell park buffer (stalled remote
                                  # emissions store here instead of wedging
                                  # the execute pipeline; drained by
                                  # routing.park_stage); 0 -> chan_cap

    # --- IO channels (paper: IO cells stream edges, 1 edge/cycle each) ---
    n_io_cells: int = 0           # 0 -> one per column (paper-style)
    io_stream_cap: int = 4096     # per-IO-cell residual stream capacity

    # --- allocation policy (paper Fig. 5) ---
    allocator: str = "vicinity"   # "vicinity" (<=2 hops) | "random"
    vicinity_hops: int = 2
    tail_insert: bool = False     # each root keeps its ghost chain's last
                                  # node and fill count, sends an insert it
                                  # has no room for straight there, and
                                  # allocates the next ghost itself, linked
                                  # behind the old tail (DESIGN §4.6);
                                  # False = the classic walk down the chain
                                  # (Listing 6), bit-exact and free

    # --- app ---
    n_vals: int = 1               # per-slot application values (BFS: level)
    qbatch: int = 1               # query-batch width (repro.mq, DESIGN §10):
                                  # the vertex value slot carries one value
                                  # per concurrent query and app-like
                                  # messages widen to vector payloads so one
                                  # diffusion wave serves all tenants.  1 =
                                  # the classic single-query engine,
                                  # bit-exact with the pre-mq machine.

    # --- engine ---
    max_cycles: int = 1_000_000
    chunk: int = 256              # cycles per jitted scan chunk / per
                                  # Pallas megakernel launch (K)
    backend: str = "jnp"          # "jnp" (lax chunk runners) | "pallas"
                                  # (fused cycle megakernel, DESIGN §6)

    # --- observability (repro.obs, DESIGN §8) ---
    telemetry: bool = False       # accumulate the per-cell/per-lane
                                  # telemetry planes inside the cycle
                                  # stages and snapshot them per chunk
                                  # into the on-device frame ring; off ->
                                  # 1x1 dummy planes, bit-exact with the
                                  # pre-telemetry engine
    frame_ring: int = 64          # frames (one per chunk) retained on
                                  # device per increment pass; older
                                  # frames are overwritten ring-style

    # --- resilience (repro.resilience, DESIGN §9) ---
    faults: object = None         # FaultPlan | None: seeded deterministic
                                  # fault injection (drop / blackout /
                                  # duplicate / corrupt) applied inside
                                  # cycle_body, plus message seals and the
                                  # end-of-increment repair pass; None ->
                                  # no fault code is traced at all,
                                  # bit-exact with the pre-fault engine
    ingest_guard: bool = False    # throttle load_stream admission from
                                  # the tm_hiw action-queue hi-water mark
                                  # (requires telemetry) so ingest backs
                                  # off under pressure instead of
                                  # manufacturing a livelock

    @property
    def n_cells(self) -> int:
        return self.height * self.width

    @property
    def root_slots(self) -> int:
        return int(math.ceil(self.n_vertices / self.n_cells))

    @property
    def primary_slots(self) -> int:
        # statically reserved rhizome-root region: slot k*root_slots + j is
        # rhizome root k of the vertex with local index j (DESIGN §4.5)
        return self.rhizome_cap * self.root_slots

    @property
    def slots(self) -> int:
        return self.primary_slots + self.ghost_slots

    @property
    def rhizome_stride(self) -> int:
        # cell offset between consecutive rhizome roots of one vertex; odd so
        # it is coprime with the (typically power-of-two) cell count and the
        # roots scatter over the mesh instead of clustering in one row
        return max(1, self.n_cells // self.rhizome_cap) | 1

    @property
    def io_cells(self) -> int:
        return self.n_io_cells if self.n_io_cells > 0 else self.width

    @property
    def lane_capacity(self) -> int:
        # per-lane ring depth: an explicit lane_cap wins, otherwise the
        # physical channel's capacity is split evenly over the lanes (the
        # classic virtual-channel organization: same buffer budget, more
        # independently-queued FIFOs)
        return self.lane_cap if self.lane_cap > 0 else \
            max(1, self.chan_cap // self.lanes)

    @property
    def park_capacity(self) -> int:
        # lanes == 1 keeps a 1-deep dummy ring (never pushed) so the
        # state stays fixed-shape without spending memory on it
        if self.lanes == 1:
            return 1
        return self.park_cap if self.park_cap > 0 else self.chan_cap

    @property
    def msg_words(self) -> int:
        # message record width in int32 words (DESIGN §10): the classic
        # 5-word record, plus one extension word per query slot beyond the
        # first.  Payload slot 0 stays in word 2 and the seal stays in
        # word 4, so the qbatch == 1 layout is byte-identical to the
        # pre-mq flit (see core/msg.py).
        from repro.core.msg import MSG_WORDS
        return MSG_WORDS + max(0, self.qbatch - 1)

    @property
    def aq_reserve(self) -> int:
        # Reserved action-queue slots so the active action's *local*
        # emissions always complete -> no self-deadlock (see DESIGN 4.2).
        # With rhizomes an app action additionally broadcasts to up to
        # rhizome_cap-1 sibling roots, any of which may be local.
        return self.edge_cap + 2 + (self.rhizome_cap - 1)

    @property
    def sys_reserve(self) -> int:
        # System actions (allocate / set-future) may fill the queue this
        # much further than application messages: combined with head
        # rotation this guarantees the future-LCO protocol always makes
        # progress under congestion (no FIFO head-of-line deadlock).
        return 2

    def validate(self) -> None:
        assert self.height >= 2 and self.width >= 2
        assert self.backend in ("jnp", "pallas"), \
            f"unknown engine backend {self.backend!r}"
        assert self.queue_cap > self.aq_reserve + self.sys_reserve + 1, \
            "queue too small for reserves (DESIGN §4.2); with rhizome_cap=" \
            f"{self.rhizome_cap} need queue_cap > " \
            f"{self.aq_reserve + self.sys_reserve + 1}"
        assert self.n_cells * self.slots < 2**31, "address overflows int32"
        assert self.edge_cap >= 1 and self.futq_cap >= 2
        assert self.lanes >= 1 and self.lane_cap >= 0 and self.park_cap >= 0
        assert self.frame_ring >= 2, \
            "frame_ring must hold >= 2 frames (the flight recorder diffs " \
            "consecutive frames, DESIGN §8)"
        assert self.lane_capacity >= 1, "lane_capacity must be >= 1"
        assert self.park_capacity >= 1, "park_capacity must be >= 1"
        assert 1 <= self.rhizome_cap <= self.n_cells, \
            "rhizome_cap must be in [1, n_cells]"
        # rhizome roots of one vertex must land on distinct cells: the k-th
        # root lives at (v + k*stride) % n_cells (DESIGN §4.5)
        cells = {(k * self.rhizome_stride) % self.n_cells
                 for k in range(self.rhizome_cap)}
        assert len(cells) == self.rhizome_cap, \
            "rhizome_stride collides rhizome roots on one cell; pick a " \
            "rhizome_cap with distinct k*stride mod n_cells"
        assert self.qbatch >= 1, "qbatch must be >= 1"
        assert self.qbatch <= 32, \
            "qbatch > 32 overflows the int32 qsel bitmask (msg word 3, " \
            "DESIGN §10); shard tenants over several sessions instead"
        assert self.qbatch > 1 or self.n_vals == 1, \
            "qbatch == 1 keeps one value per slot (vals is [H,W,S])"
        if self.qbatch > 1:
            assert self.faults is None, \
                "faults + qbatch > 1 is unsupported: the OP_REPAIR io " \
                "sentinel rows carry a single value word (DESIGN §9/§10); " \
                "run fault injection on a qbatch=1 engine"
            assert self.n_vals == self.qbatch, \
                "qbatch > 1 requires n_vals == qbatch (the query axis IS " \
                "the value axis; StreamingEngine sets both from the app)"
        if self.faults is not None:
            self.faults.validate(self)
        if self.ingest_guard:
            assert self.telemetry, \
                "ingest_guard needs the tm_hiw telemetry plane " \
                "(set telemetry=True, DESIGN §9)"
        if self.rhizome_cap > 1:
            # a rhizome activation drains up to futq_cap deferred inserts
            # back onto the LOCAL action queue in one action; the drain
            # must fit the local-emission reserve (DESIGN §4.2/§4.5)
            assert self.futq_cap <= self.aq_reserve, \
                f"futq_cap={self.futq_cap} exceeds the local-emission " \
                f"reserve {self.aq_reserve}; shrink futq_cap or raise " \
                "edge_cap/rhizome_cap"
        if self.tail_insert:
            # a new tail receives the inserts deferred on its root's
            # future queue; they must fit it, or the tail would grow a
            # ghost of its own behind the root's back (DESIGN §4.6)
            assert self.futq_cap <= self.edge_cap, \
                f"tail_insert needs futq_cap <= edge_cap, got " \
                f"{self.futq_cap} > {self.edge_cap}"
