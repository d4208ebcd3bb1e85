"""device.idle_share.dense: 1 - (union of device-operation intervals) /
(traced window), in percent, in the dense cells."""


def read(ctx):
    tr = ctx["trace"]
    return None if tr is None else 100.0 * tr["idle_share"]
