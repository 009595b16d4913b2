"""Seconds per ALS iteration: every second of the measured window over
every iteration that `decompose` completed in it.  Each iteration ends in
the fit that `drive` reads back from the device, and each job's result is
on the host side of a `block_until_ready` before the next job starts."""


def read(r):
    return r.window_s / r.iterations if r.iterations else None
