"""Scheduler and runtime: mean from a query's due time to the end
of its schedule span, ms."""

from chipbench import layers


def read(w):
    return layers.queue_wait_ms(w)
