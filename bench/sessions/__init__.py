"""How a configuration serves its standing queries: one module per
``session`` kind named in a configuration file.  Each gives
``open(cfg, queries) -> session`` where the session has
``run(edges) -> IncrementResult``, ``warm()`` and ``values(q)``."""
