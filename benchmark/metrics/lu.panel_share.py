"""lu.panel_share: device time of the ``getrf/panel`` scope (the no-pivot
diagonal block, the L21 and U12 solves, and the rolled panel loops' own
control) over busy time in the traced window, in percent
(``scopes.readings``)."""


def read(ctx):
    return (ctx["scopes"] or {}).get("panel_share")
