"""``device_idle_pct.traces_init``: share of the traced window in which the
chip runs no operation, in %, for cells that drive ``run_traces_init``."""


def read(r):
    t = r.trace
    if r.entry != "run_traces_init" or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
