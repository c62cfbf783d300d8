"""Record ``tpu_v5e_tiny.xplane.pb``, the reducer's recorded fixture:
three runs of one small jitted program, each inside a ``bench.call``
span, inside ``bench.window``.  Run on a machine with a TPU:

    python bench/tests/data/record_tiny.py <output directory>
"""

import shutil
import sys
import time

import jax
import jax.numpy as jnp


def tiny(a):
    return jnp.tanh(a @ a).sum()


def main(out: str) -> None:
    f = jax.jit(tiny)
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(out + "/raw", profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.call"):
                f(x).block_until_ready()
            time.sleep(0.002)
    jax.profiler.stop_trace()
    sys.path.insert(0, ".")
    from bench import trace_reduce
    src = trace_reduce.find_xplane(out + "/raw")
    shutil.copy(src, out + "/tpu_v5e_tiny.xplane.pb")
    print(trace_reduce.reduce(src))


if __name__ == "__main__":
    main(sys.argv[1])
