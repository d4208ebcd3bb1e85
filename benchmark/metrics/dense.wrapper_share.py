"""dense.wrapper_share: device time of the scopes whose role is ``wrapper``
(the phases that only move or mask data around the factor and the sweeps:
potrf's prep, mask and store, potrs's prep and store) over busy time in the
traced window, in percent (``scopes.readings``)."""


def read(ctx):
    return (ctx["scopes"] or {}).get("wrapper_share")
