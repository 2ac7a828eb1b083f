"""Telemetry exporter (DESIGN §8).

:func:`congestion_heatmap` renders a :class:`repro.obs.FrameLog` as
per-cell [H,W] planes of the increment's cumulative activity (arrivals,
execs, stalls, lane occupancy integral, blocked cycles, queue hi-water
marks), the JSON dump that ``benchmarks/report.py --section
congestion`` renders; :func:`write_heatmap` writes it under
``results/profile/``.
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

from repro.core.config import EngineConfig
from repro.core.state import (N_TM_STAGES, TM_HW_AQ, TM_HW_PK, TM_L_BLOCK,
                              TM_L_GRANT, TM_L_OCC)
from repro.obs.frames import FS_CYCLE, FrameLog

# index order matches the TM_* stage constants in core.state
STAGE_NAMES = ("exec", "alloc", "stall", "hop", "stage",
               "park", "unpark", "io", "bcast")
assert len(STAGE_NAMES) == N_TM_STAGES


def congestion_heatmap(cfg: EngineConfig, frames: FrameLog) -> dict:
    """Per-cell congestion planes of the increment (final frame's
    cumulative counters), as JSON-ready nested lists."""
    last = frames.last()
    cell, lane, hiw = last["cell"], last["lane"], last["hiw"]
    # cycle span of the log (frame 0 = increment-start baseline)
    cycles = max(1, int(frames.scal[-1][FS_CYCLE]
                        - frames.scal[0][FS_CYCLE]))

    def plane(a):
        return np.asarray(a).astype(int).tolist()

    return dict(
        grid=[cfg.height, cfg.width], lanes=cfg.lanes, cycles=cycles,
        frames=len(frames), dropped=frames.dropped,
        # [H,W] planes
        stages={n: plane(cell[..., i]) for i, n in enumerate(STAGE_NAMES)},
        lane_occ_integral=plane(lane[..., TM_L_OCC].sum(axis=(-2, -1))),
        lane_blocked=plane(lane[..., TM_L_BLOCK].sum(axis=(-2, -1))),
        lane_grants=plane(lane[..., TM_L_GRANT].sum(axis=(-2, -1))),
        aq_hiwater=plane(hiw[..., TM_HW_AQ]),
        pk_hiwater=plane(hiw[..., TM_HW_PK]))


def write_heatmap(path, cfg: EngineConfig, frames: FrameLog) -> str:
    p = pathlib.Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(json.dumps(congestion_heatmap(cfg, frames), indent=1))
    return str(p)
