"""``snp_step_roofline.traces_init``: ``snp_step_roofline`` for cells that
drive ``run_traces_init``: the least time the chip needs for the work its
``run_traces`` calls of the traced window require (``bench.roofline``,
the per-trace starts included) over the device's busy time, in %."""


def read(r):
    busy = r.trace["busy_s"]
    if r.entry != "run_traces_init" or not r.least_time_s or busy <= 0:
        return None
    return 100.0 * r.least_time_s / busy
