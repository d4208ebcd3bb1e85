"""dense.sweep_roofline: the least time of the job the ``sweep`` role is
measured against (potrs: two triangular sweeps) over the device time of the
forward and backward scopes per solve in the traced window, in percent
(``scopes.readings``)."""


def read(ctx):
    return (ctx["scopes"] or {}).get("sweep_roofline")
