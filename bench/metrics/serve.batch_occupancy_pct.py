"""``serve.batch_occupancy_pct``: traces served over device-batch slots
(device calls times the service's batch size) in the window, from the
service's own counters, in %."""


def read(r):
    c = r.counters
    if r.entry != "service" or not c.get("device_calls"):
        return None
    return 100.0 * c["traces_served"] / (c["device_calls"] * c["batch_size"])
