"""device_idle: the share of the traced window in which no operation
ran on the device (1 - union of op intervals / window)."""


def read(ctx):
    t = ctx.get("trace")
    return 100.0 * t["idle_share"] if t else None
