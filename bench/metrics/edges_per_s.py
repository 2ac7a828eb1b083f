"""edges_per_s: edges of every batch done in the closed-loop window over
the window's whole length, each standing query kept exact."""
from bench.readings import edges_per_s as read  # noqa: F401
