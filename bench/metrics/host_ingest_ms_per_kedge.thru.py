"""Host milliseconds in ``repro.load_stream`` per thousand edges."""
from bench.stages import host_ingest_ms_per_kedge as read  # noqa: F401
