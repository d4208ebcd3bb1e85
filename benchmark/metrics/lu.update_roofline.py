"""lu.update_roofline: the least time of the job the ``update`` role is
measured against (getrf; at nb=256 the trailing update does ~97.7% of its
flops) over the device time of the ``getrf/update`` scope per solve in the
traced window, in percent (``scopes.readings``)."""


def read(ctx):
    return (ctx["scopes"] or {}).get("update_roofline")
