"""Device microseconds of scope ``cca.park`` per machine cycle."""
from bench.stages import stage_us_per_cycle


def read(view):
    return stage_us_per_cycle(view, "cca.park")
