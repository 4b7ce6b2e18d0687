"""Record the small trace that test_bench_trace.py reads.

    python3 tests/bench/data/record_trace.py <out.xplane.pb>

Run on a TPU: a window span holding three executions of one jitted program
(a Pallas kernel named ``tiny_kernel`` and an XLA fusion), with host spans
between them, written as one ``.xplane.pb``.
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


@jax.jit
def step(x):
    y = pl.pallas_call(_kernel, out_shape=jax.ShapeDtypeStruct(x.shape,
                                                                x.dtype),
                       name="tiny_kernel")(x)
    return jnp.tanh(y) + 1.0


def main(out: str) -> None:
    x = jnp.ones((256, 512), jnp.float32)
    step(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench:window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench:submit"):
                    step(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench:sleep"):
                    time.sleep(0.002)
        jax.profiler.stop_trace()
        shutil.copy(next(Path(tmp).glob("plugins/profile/*/*.xplane.pb")),
                    out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
