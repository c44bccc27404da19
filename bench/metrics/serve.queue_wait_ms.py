"""``serve.queue_wait_ms``: mean wait of a request in the service's queue,
from its submit to the start of the flush that took it, in ms, from the
service's own counters (``queue_wait_us`` over ``queued_requests``)."""


def read(r):
    c = r.counters
    if r.entry != "service" or not c.get("queued_requests"):
        return None
    return c["queue_wait_us"] / c["queued_requests"] / 1e3
