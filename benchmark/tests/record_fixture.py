"""How ``fixture.xplane.pb`` was recorded (on the chip, by hand):

    chiprun -- python3 benchmark/tests/record_fixture.py

Three "steps" of a small jitted program (a matmul, a tanh, a sum) under
the benchmark's own markers, each followed by a ``save_call`` span in which
the host sleeps 20 ms with the device idle. Small on purpose: the file is
kept in the repository and read by ``test_reduce_trace.py``.
"""

import glob
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    import jax
    import jax.numpy as jnp

    from benchmark import reduce_trace

    out = os.path.join(ROOT, "chiprun_out", "fixture")
    shutil.rmtree(out, ignore_errors=True)
    step = jax.jit(lambda x: jnp.tanh(x @ x).sum(), )
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    step(x).block_until_ready()
    reduce_trace.start_trace(out)
    for i in range(3):
        with jax.profiler.TraceAnnotation("step_dispatch"):
            y = step(x)
        y.block_until_ready()
        with jax.profiler.TraceAnnotation("save_call"):
            time.sleep(0.02)
    reduce_trace.stop_trace()
    path = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(path, os.path.join(out, "fixture.xplane.pb"))
    print(jax.devices()[0].device_kind, os.path.getsize(path), "bytes")
    reduce_trace.describe(path)


if __name__ == "__main__":
    main()
