"""Compliant siblings of metrics_bad.py."""

import time

from igaming_platform_tpu.obs.metrics import Registry

registry = Registry()

txns = registry.counter(name="txns_total", help_text="Transactions scored")
lat = registry.histogram("latency_ms", "Request latency in milliseconds")


def timed_dispatch(fn, x):
    # Timing dispatch WITHOUT block_until_ready inside the clock
    # bracket is fine.
    t0 = time.perf_counter()
    y = fn(x)
    t1 = time.perf_counter()
    y.block_until_ready()
    return (t1 - t0, y)
