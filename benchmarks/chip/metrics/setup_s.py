"""Seconds from process start to the start of the measured window: JAX and
chip start-up, tensor generation, plan build and layout transfer, compiles
(from the persistent cache after a checkout's first run) and a warm-up
job."""


def read(r):
    return r.setup_s
