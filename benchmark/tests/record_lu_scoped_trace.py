"""Record the small scoped chip trace of the dense general solve, for the
self-tests' traced rehearsal of ``gesv_n16384``.

    python3 benchmark/tests/record_lu_scoped_trace.py <out_prefix>

Runs on a TPU: the configuration's public ``gesv`` program (tournament-
pivoted LU, then getrs) at n=512 with nb=128 and 16 right-hand sides, three
steps inside a host annotation ``bench.window``, each step a ``bench.call``
and a ``bench.sync`` with a ``bench.host`` pause of about 2 ms between
steps, as ``record_scoped_trace.py`` records posv.  Writes
``<out_prefix>.xplane.pb`` and ``<out_prefix>.hlo.txt`` (without its tables
of source paths) and prints the scope reduction as JSON.
"""

import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE),
                os.path.dirname(os.path.dirname(HERE))]

N, NB, NRHS = 512, 128, 16
CONFIG = os.path.join(os.path.dirname(HERE), "configs",
                      "dense_general_solve")


def main(prefix: str) -> int:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("record_lu_scoped_trace: needs a TPU", file=sys.stderr)
        return 2
    # a program cached by a tree whose scopes differ would keep their names
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    from benchlib.harness import load_module
    from benchlib.scopes import reduce_scopes
    from benchlib.trace import find_xplane
    from record_scoped_trace import without_source_tables

    with open(CONFIG + ".json") as f:
        config = json.load(f)
    gesv = load_module(CONFIG + ".py", "dense_general_solve").System(
        dict(config, n=N, nb=NB),
        {"nrhs": NRHS, "routine": "gesv", "matrices": 1, "rhs_blocks": 1},
        0).programs()["gesv"]
    k = jax.random.split(jax.random.key(0))
    a = jax.random.uniform(k[0], (N, N), jnp.float32)
    b = jax.random.uniform(k[1], (N, NRHS), jnp.float32)
    exe = jax.jit(gesv).lower(a, b).compile()
    jax.block_until_ready(exe(a, b))
    tmp = tempfile.mkdtemp(dir=os.path.dirname(os.path.abspath(prefix)))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.call"):
                y = exe(a, b)
            with jax.profiler.TraceAnnotation("bench.sync"):
                jax.block_until_ready(y)
            with jax.profiler.TraceAnnotation("bench.host"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    shutil.copyfile(find_xplane(tmp), prefix + ".xplane.pb")
    shutil.rmtree(tmp)
    with open(prefix + ".hlo.txt", "w") as f:
        f.write(without_source_tables(exe.as_text()))
    print(json.dumps(reduce_scopes(prefix + ".xplane.pb", [exe.as_text()],
                                   config["scopes"]["drivers"])))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
