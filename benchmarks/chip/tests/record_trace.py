"""Record the small profiler trace that ``test_chip_trace.py`` reads.

Run on a chip: ``python3 benchmarks/chip/tests/record_trace.py <out.xplane.pb>``.
It traces three host spans: ``busy`` runs a jitted matmul and a Pallas
kernel named ``_double_kernel`` back to back, ``wait`` sleeps 50 ms with
the device idle, and ``busy`` runs once more.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _double_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def double(x):
    return pl.pallas_call(_double_kernel,
                          out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)


def main(out: str):
    mm = jax.jit(lambda a: a @ a)
    dbl = jax.jit(double)
    x = jnp.ones((1024, 1024), jnp.float32)
    jax.block_until_ready((mm(x), dbl(x)))            # compile outside
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    for name in ("busy", "wait", "busy"):
        with jax.profiler.TraceAnnotation(name):
            if name == "busy":
                jax.block_until_ready(dbl(mm(x)))
            else:
                time.sleep(0.05)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(path, out)
    shutil.rmtree(d)


if __name__ == "__main__":
    main(sys.argv[1])
