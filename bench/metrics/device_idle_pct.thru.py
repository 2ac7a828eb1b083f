"""Percent of the traced window in which no program ran on the device."""
from bench.readings import device_idle_pct as read  # noqa: F401
