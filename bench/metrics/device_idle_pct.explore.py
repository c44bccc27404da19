"""``device_idle_pct.explore``: share of the traced window in which the chip
runs no operation (averaged over the chips), in %, for cells that drive
``explore`` or ``explore_distributed``."""


def read(r):
    t = r.trace
    if r.entry not in ("explore", "explore_distributed") or \
            t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
