"""Q standing queries served by one ``MQSession``, all admitted before the
stream: the entry is ``MQSession.run_increment``."""
from __future__ import annotations

import numpy as np


class MQSessionAdapter:
    def __init__(self, cfg, queries):
        import jax
        from repro.mq.session import MQSession
        self.ses = MQSession(cfg, qbatch=len(queries),
                             apps=[q["app"] for q in queries])
        for slot, q in enumerate(queries):
            self.ses.admit(q["app"], q["source"], slot=slot)
        self.n = self.ses.eng.cfg.n_vertices
        # host span over the session's fold of the per-slot counters: it
        # opens when the engine's increment returns inside
        # MQSession.run_increment and closes when that call returns
        self._fold = None
        engine_increment = self.ses.eng.run_increment

        def engine_then_fold(*a, **kw):
            res = engine_increment(*a, **kw)
            self._fold = jax.profiler.TraceAnnotation("bench.mq_fold")
            self._fold.__enter__()
            return res
        self.ses.eng.run_increment = engine_then_fold

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
        try:
            return self.ses.run_increment(edges)
        finally:
            if self._fold is not None:
                self._fold.__exit__(None, None, None)
                self._fold = None

    def values(self, q: int) -> np.ndarray:
        return self.ses.values(q, self.n)

    def close(self):
        self.ses = None


def open(cfg, queries):
    return MQSessionAdapter(cfg, queries)
