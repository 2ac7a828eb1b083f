"""One standing query on one ``StreamingEngine``: the entry is
``StreamingEngine.run_increment``."""
from __future__ import annotations

import numpy as np


class EngineSession:
    def __init__(self, cfg, queries):
        from repro.core.engine import StreamingEngine
        if len(queries) != 1:
            raise ValueError("an engine session serves exactly one query")
        (q,) = queries
        self.eng = StreamingEngine(cfg, q["app"])
        self.eng.seed(q["source"], 0.0)
        self.n = self.eng.cfg.n_vertices

    @property
    def cfg(self):
        return self.eng.cfg

    def warm(self):
        """Compile (or load from the cache) this cell's device loop with
        one call on an empty increment."""
        self.run(np.zeros((0, 3), np.int32))

    def run(self, edges):
        return self.eng.run_increment(edges)

    def values(self, q: int) -> np.ndarray:
        return self.eng.values(self.n)

    def close(self):
        self.eng = None


def open(cfg, queries):
    return EngineSession(cfg, queries)
