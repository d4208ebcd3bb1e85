"""The ``sequential`` loop: back-to-back steps, each made ready before the
next is issued, as a caller that uses each answer does.

A loop is found by the name a traffic file gives under ``"loop"``: the
harness loads ``loops/<loop>.py`` and calls its ``run``.  It drives a
configuration's ``System`` for the measured window and returns what the
host clock saw.  ``hooks.start()`` runs as the window opens and
``hooks.stop()`` once ``hooks.due()`` or as the window closes (the harness
runs the profiler and reads the program's counters there).  Every call into
the system runs inside a host annotation named ``bench.<what>``, so a trace
can say what the host was doing while the device sat idle.
"""

from __future__ import annotations

import time

import numpy as np


def run(system, traffic: dict, seconds: float, seed: int, hooks,
        annotate) -> dict:
    """Steps ``system.step(0), system.step(1), ...`` until ``seconds`` have
    passed.  Keeps a uniform sample (a reservoir drawn from the seed) of
    ``check_sample`` steps' outputs for the check."""
    import jax

    k = int(traffic["check_sample"])
    rng = np.random.default_rng([seed, 0xc4ec])
    kept = []
    hooks.start()
    t0 = time.perf_counter()
    i = 0
    while True:
        with annotate("bench.call"):
            out = system.step(i)
        with annotate("bench.sync"):
            jax.block_until_ready(out)
        t = time.perf_counter()
        if i < k:
            kept.append((i, out))
        else:
            j = int(rng.integers(i + 1))
            if j < k:
                kept[j] = (i, out)
        i += 1
        if hooks.due(t):
            hooks.stop(done=i)
        if t - t0 >= seconds:
            break
    hooks.stop(done=i)
    return {"t0": t0, "t_end": t, "window_s": t - t0, "steps": i,
            "kept": kept, "attempted": i, "failed": 0}
