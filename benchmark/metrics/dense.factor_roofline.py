"""dense.factor_roofline: the least time of the job the ``factor`` role is
measured against (potrf; ``benchlib/jobs.py`` and ``peaks.json``) over the
device time of the factor scopes per solve in the traced window, in
percent; nothing where the window runs no factorization
(``scopes.readings``)."""


def read(ctx):
    return (ctx["scopes"] or {}).get("factor_roofline")
