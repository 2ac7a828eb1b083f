"""Q standing queries served by one ``MQSession``, all admitted before the
stream: the entry is ``MQSession.run_increment``."""
from __future__ import annotations

import numpy as np


class MQSessionAdapter:
    def __init__(self, cfg, queries):
        from repro.mq.session import MQSession
        self.ses = MQSession(cfg, qbatch=len(queries),
                             apps=[q["app"] for q in queries])
        for slot, q in enumerate(queries):
            self.ses.admit(q["app"], q["source"], slot=slot)
        self.n = self.ses.eng.cfg.n_vertices

    @property
    def cfg(self):
        return self.ses.eng.cfg

    @property
    def eng(self):
        return self.ses.eng

    def warm(self):
        """Compile (or load from the cache) this cell's device loop with
        one call on an empty increment; it also relaxes the admitted
        seeds, which touch no edge."""
        self.run(np.zeros((0, 3), np.int32))

    def run(self, edges):
        return self.ses.run_increment(edges)

    def values(self, q: int) -> np.ndarray:
        return self.ses.values(q, self.n)

    def close(self):
        self.ses = None


def open(cfg, queries):
    return MQSessionAdapter(cfg, queries)
