"""Machine cycles per thousand edges ingested in the window, from the
engine's IncrementResult counts (the same on every platform)."""
from bench.readings import machine_cycles_per_kedge as read  # noqa: F401
