"""Padded slots over all slots of every mode's BlockPlan, in percent: a
count the program's plans carry (nonzeros and nblocks * blk per mode)."""


def read(r):
    return 100.0 * r.padded_slots / r.slots if r.slots else None
