"""``compile_s``: seconds of set-up covered by JAX's trace, lowering and
backend-compile events (their union), from ``bench.compile_timing``."""


def read(r):
    return r.compile_s
