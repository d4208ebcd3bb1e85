"""dense_solve_ms: the window's time over the solves completed in it, each
made ready before the next was issued (host clock)."""


def read(ctx):
    steps = ctx["obs"].get("steps")
    if not steps:
        return None
    return ctx["obs"]["window_s"] * 1e3 / steps
