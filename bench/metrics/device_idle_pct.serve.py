"""``device_idle_pct.serve``: share of the traced window in which the chip
runs no operation, in %, for cells that drive ``service``."""


def read(r):
    t = r.trace
    if r.entry != "service" or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
