"""``serve.flush_host_pct``: share of the service's flush time spent outside
the device call (readback, slicing, resolving futures), in %, from the
service's own counters (``flush_us`` and ``flush_device_us``)."""


def read(r):
    c = r.counters
    if r.entry != "service" or not c.get("flush_us") \
            or "flush_device_us" not in c:
        return None
    return 100.0 * (c["flush_us"] - c["flush_device_us"]) / c["flush_us"]
