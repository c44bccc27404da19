"""``snp_step_roofline``: the least time the chip needs for the work the
``run_traces`` calls of the traced window require (``bench.roofline``),
over the device's busy time in that window, in %."""


def read(r):
    busy = r.trace["busy_s"]
    if r.entry != "run_traces" or not r.least_time_s or busy <= 0:
        return None
    return 100.0 * r.least_time_s / busy
