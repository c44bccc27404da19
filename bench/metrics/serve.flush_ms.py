"""``serve.flush_ms``: mean wall time of a service flush, from the start of
the chunk to its last future resolved, in ms, from the service's own
counters (``flush_us`` over ``device_calls``)."""


def read(r):
    c = r.counters
    if r.entry != "service" or "flush_us" not in c \
            or not c.get("device_calls"):
        return None
    return c["flush_us"] / c["device_calls"] / 1e3
