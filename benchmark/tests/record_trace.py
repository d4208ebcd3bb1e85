"""Record the small chip trace that ``test_trace.py`` reduces.

    python3 benchmark/tests/record_trace.py <out.xplane.pb>

Runs on a TPU: three steps of a jitted matrix product inside a host
annotation ``bench.window``, each step a ``bench.call`` and a
``bench.sync``, with a ``bench.host`` pause of about 2 ms between steps so
that the device sits idle while the host is busy.  Writes the profiler's
``.xplane.pb`` to the path given and prints the reduction as JSON.
"""

import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    from benchlib.trace import find_xplane, reduce_trace

    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((2048, 2048), jnp.float32)
    jax.block_until_ready(f(x))
    tmp = tempfile.mkdtemp(dir=os.path.dirname(os.path.abspath(out)))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.call"):
                y = f(x)
            with jax.profiler.TraceAnnotation("bench.sync"):
                jax.block_until_ready(y)
            with jax.profiler.TraceAnnotation("bench.host"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    shutil.copyfile(find_xplane(tmp), out)
    shutil.rmtree(tmp)
    import json

    print(json.dumps(reduce_trace(out)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
