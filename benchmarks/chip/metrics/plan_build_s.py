"""Host seconds of the workspace build in set-up: one BlockPlan per mode
(`core/remap.plan_blocks`) and its transfer to the device, until the
layouts are on the device."""


def read(r):
    return r.plan_build_s
