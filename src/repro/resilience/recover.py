"""Livelock recovery: boundary snapshots, config escalation, migration.

A livelock (DESIGN §4.2) is a *sizing* failure — the workload's message
dependency depth exceeded the buffer budget — so retrying the identical
configuration deterministically wedges again.  The recovery protocol
(DESIGN §9) therefore escalates: restore the last increment-boundary
state, re-run the increment under a relieved config (more virtual lanes,
then a deeper action queue), with exponential backoff between attempts
and the flight-recorder wedge report logged per attempt
(``StreamingEngine.recovery_log``).

Escalation changes ``lanes``/``queue_cap``, which changes the channel /
park / action-queue leaf *shapes* — the boundary state cannot be loaded
verbatim.  :func:`migrate_state` exploits that an increment boundary is
*quiescent* (every queue, channel, park ring, future queue and active
register is empty — that is the definition of quiescence): only the
durable storage leaves carry information, and their shapes are invariant
under lanes/queue_cap relief, so migration is a straight copy into a
fresh ``init_state`` of the new config.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core.state import MachineState, init_state

# Leaves that carry durable information at a quiescent boundary; every
# other leaf is provably empty/zero there (see quiescent()) or is a
# counter the increment restart resets anyway.  Shapes depend only on
# the grid/slot geometry, never on lanes/queue_cap — asserted below.
STORAGE_LEAVES = ("vals", "nedges", "edst", "ew", "gaddr", "gstate",
                  "rhz_on", "rstate", "nfree", "arot", "gtail", "tcnt")


@dataclasses.dataclass(frozen=True)
class RecoveryPolicy:
    """Retry policy for ``run_increment(recover=...)``.

    Attempt ``k`` (1-based) re-runs the increment from the boundary
    snapshot under ``lanes = base + k * lanes_step`` and ``queue_cap =
    base + k * queue_cap_step``, after sleeping ``backoff_s * 2**(k-1)``
    seconds.  After ``max_attempts`` retries the original
    :class:`LivelockError` is re-raised, augmented with the attempt log.
    """
    max_attempts: int = 3
    backoff_s: float = 0.0
    lanes_step: int = 1
    queue_cap_step: int = 0

    def escalate(self, base_cfg, k: int):
        """The attempt-``k`` relief config derived from ``base_cfg``."""
        kw = {}
        if self.lanes_step:
            kw["lanes"] = base_cfg.lanes + k * self.lanes_step
        if self.queue_cap_step:
            kw["queue_cap"] = base_cfg.queue_cap + k * self.queue_cap_step
        new = dataclasses.replace(base_cfg, **kw)
        new.validate()
        return new


def assert_boundary(st: MachineState) -> None:
    """Raise unless ``st`` is a quiescent increment boundary (the only
    state from which :func:`migrate_state` is sound)."""
    pending = {
        "action queues": int(np.sum(np.asarray(st.aq_n))),
        "channels": int(np.sum(np.asarray(st.ch_n))),
        "park rings": int(np.sum(np.asarray(st.pk_n))),
        "future queues": int(np.sum(np.asarray(st.fq_n))),
        "active actions": int(np.sum(np.asarray(st.cvalid))),
        "coalesced forwards": int(np.sum(np.asarray(st.fwd_pending))),
        "io stream": int(np.sum(np.asarray(st.io_n) - np.asarray(st.io_pos))),
    }
    busy = {k: v for k, v in pending.items() if v}
    if busy:
        raise ValueError(
            "recovery snapshot is not an increment boundary — migration "
            f"is only sound at quiescence (pending work: {busy})")


def migrate_state(new_cfg, app, snapshot: MachineState,
                  strict: bool = True) -> MachineState:
    """Carry a quiescent boundary ``snapshot`` into a fresh machine of
    ``new_cfg`` (typically an escalated lanes/queue_cap relief config).

    Copies only :data:`STORAGE_LEAVES`; queues/channels/registers start
    empty (they *were* empty — quiescence) and counters/telemetry reset
    with the increment restart.
    """
    if strict:
        assert_boundary(snapshot)
    fresh = init_state(new_cfg, init_vals=app.init_val,
                       fwd_init=app.fwd_neutral)
    moved = {}
    for name in STORAGE_LEAVES:
        src = np.asarray(getattr(snapshot, name))
        dst = getattr(fresh, name)
        if src.shape != dst.shape:
            raise ValueError(
                f"cannot migrate leaf '{name}': shape {src.shape} -> "
                f"{dst.shape}; escalation may only change lanes / "
                "queue_cap-class capacities, not the grid or slot layout")
        moved[name] = jnp.asarray(src).astype(dst.dtype)
    return fresh._replace(cycle=jnp.asarray(np.asarray(snapshot.cycle),
                                            jnp.int32), **moved)
