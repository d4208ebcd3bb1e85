"""device.host_gap_ms: device idle time between program executions per
solve in the traced window, in ms: the wake from the host's wait and the
next launch (``scopes.reduce_scopes``' ``between_programs_s``)."""


def read(ctx):
    return (ctx["scopes"] or {}).get("host_gap_ms")
