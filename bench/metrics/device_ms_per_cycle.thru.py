"""Device milliseconds of the engine's device loop per machine cycle,
from the profiler trace of the window and the engine's cycle counts."""
from bench.readings import device_ms_per_cycle as read  # noqa: F401
