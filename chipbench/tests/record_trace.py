"""Record the small trace that ``test_trace_reduce.py`` reads, on a TPU.

    python chipbench/tests/record_trace.py chipbench/tests/data/small.xplane.pb

Three steps, each a jitted matrix product under a ``train_step`` span and
its wait under ``block``, after a 20 ms host sleep under ``loader_get`` in
which the device has nothing to do; all inside a ``window`` span.
"""
import glob
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out: str) -> None:
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((4096, 4096), jnp.bfloat16)
    jax.block_until_ready(f(x))
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("loader_get"):
                time.sleep(0.02)
            with jax.profiler.TraceAnnotation("train_step"):
                y = f(x)
            with jax.profiler.TraceAnnotation("block"):
                jax.block_until_ready(y)
    jax.profiler.stop_trace()
    shutil.copy(glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0], out)
    shutil.rmtree(d)


if __name__ == "__main__":
    main(sys.argv[1])
