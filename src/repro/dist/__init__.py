"""repro.dist — the distribution layer (DESIGN §5).

One CCA state / model pytree, sharded across a device mesh by GSPMD,
behind a single programming abstraction:

* :mod:`repro.dist.ctx`      — process-global mesh registry + ``constrain``
* :mod:`repro.dist.sharding` — per-family sharding rules (CCA state,
  LM, GNN, DLRM) + ``pad_to``
* :mod:`repro.dist.pipeline` — microbatch pipeline parallelism
"""
