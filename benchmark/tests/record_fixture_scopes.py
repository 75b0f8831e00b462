"""How ``fixture_scopes.xplane.pb`` was recorded (on the chip, by hand):

    chiprun -- python3 benchmark/tests/record_fixture_scopes.py

Three steps of a small jitted program shaped like a train step: a
``value_and_grad`` over a ``scan`` whose body holds two named scopes
(``fix.mix``: a product and a tanh; ``fix.gate``: a sigmoid gate), a loss
under ``train.loss``, then the update under ``train.optimizer``. Small on
purpose: the file is kept in the repository and read by
``test_trace_scopes.py``, which holds the shares printed here.
"""

import glob
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    import jax
    import jax.numpy as jnp

    from benchmark import reduce_trace, trace_scopes

    def loss(w, x):
        def body(h, _):
            with jax.named_scope("fix.mix"):
                h = jnp.tanh(h @ w)
            with jax.named_scope("fix.gate"):
                h = h * jax.nn.sigmoid(h)
            return h, ()

        h, _ = jax.lax.scan(body, x, None, length=4)
        with jax.named_scope("train.loss"):
            return jnp.mean(jnp.square(h.astype(jnp.float32)))

    def step(w, x):
        value, grad = jax.value_and_grad(loss)(w, x)
        with jax.named_scope("train.optimizer"):
            w = w - (0.01 * grad).astype(w.dtype)
        return w, value

    out = os.path.join(ROOT, "chiprun_out", "fixture_scopes")
    shutil.rmtree(out, ignore_errors=True)
    step = jax.jit(step)
    w = jnp.eye(1024, dtype=jnp.bfloat16) * 0.5
    x = jnp.ones((2048, 1024), jnp.bfloat16)
    w, value = step(w, x)
    value.block_until_ready()
    reduce_trace.start_trace(out)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("step_dispatch"):
            w, value = step(w, x)
        value.block_until_ready()
    reduce_trace.stop_trace()
    path = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)[0]
    kept = os.path.join(out, "fixture_scopes.xplane.pb")
    shutil.copy(path, kept)
    shutil.rmtree(os.path.join(out, "plugins"))
    print(jax.devices()[0].device_kind, os.path.getsize(kept), "bytes")
    trace_scopes.main([kept, "--depth", "4"])
    trace_scopes.main([kept, "--json"])


if __name__ == "__main__":
    main()
