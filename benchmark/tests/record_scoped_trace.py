"""Record the small scoped chip trace that ``test_scopes.py`` reduces.

    python3 benchmark/tests/record_scoped_trace.py <out_prefix>

Runs on a TPU: the dense cell's public ``posv`` program at n=512 with 16
right-hand sides, three steps inside a host annotation ``bench.window``,
each step a ``bench.call`` and a ``bench.sync`` with a ``bench.host`` pause
of about 2 ms between steps.  Writes the profiler's trace to
``<out_prefix>.xplane.pb`` and the compiled program's HLO text to
``<out_prefix>.hlo.txt`` (without its tables of source paths), and prints
the scope reduction as JSON.
"""

import json
import os
import re
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))]

N, NRHS = 512, 16


def without_source_tables(text: str) -> str:
    """The HLO text without its tables of source files and lines, which
    name the paths of the checkout it was recorded in; every instruction
    keeps its ``op_name``."""
    return re.sub(r"(?m)^(FileNames|FunctionNames|FileLocations|StackFrames)"
                  r"\n(?:.+\n)*\n?", "", text)


def main(prefix: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("record_scoped_trace: needs a TPU", file=sys.stderr)
        return 2
    # a program cached by a tree whose scopes differ would keep their names
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    from benchlib.harness import load_module
    from benchlib.scopes import reduce_scopes
    from benchlib.trace import find_xplane

    cfg = load_module(os.path.join(os.path.dirname(HERE), "configs",
                                   "dense_spd_solve.py"), "dense_spd_solve")
    posv = cfg.System({"n": N, "dtype": "float32"},
                      {"nrhs": NRHS, "routine": "posv", "matrices": 1,
                       "rhs_blocks": 1}, 0).programs()["posv"]
    rng = np.random.default_rng(0)
    r = rng.standard_normal((N, N)).astype(np.float32)
    a = jnp.asarray(r @ r.T + N * np.eye(N, dtype=np.float32))
    b = jnp.asarray(rng.standard_normal((N, NRHS)).astype(np.float32))
    exe = jax.jit(posv).lower(a, b).compile()
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
    with open(os.path.join(os.path.dirname(HERE), "configs",
                           "dense_spd_solve.json")) as f:
        drivers = json.load(f)["scopes"]["drivers"]
    print(json.dumps(reduce_scopes(prefix + ".xplane.pb", [exe.as_text()],
                                   drivers)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
