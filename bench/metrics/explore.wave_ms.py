"""``explore.wave_ms``: device busy milliseconds in the traced window per
breadth-first wave (the sum of ``ExploreResult.steps`` over its calls)."""


def read(r):
    if r.entry not in ("explore", "explore_distributed") or not r.waves:
        return None
    return r.trace["busy_s"] * 1e3 / r.waves
