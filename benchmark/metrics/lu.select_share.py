"""lu.select_share: device time of the ``getrf/select`` scope (the
tournament's batched merge LUs, or the pp panel LU) over busy time in the
traced window, in percent (``scopes.readings``)."""


def read(ctx):
    return (ctx["scopes"] or {}).get("select_share")
