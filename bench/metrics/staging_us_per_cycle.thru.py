"""Device microseconds of scope ``cca.staging`` per machine cycle."""
from bench.stages import stage_us_per_cycle


def read(view):
    return stage_us_per_cycle(view, "cca.staging")
