"""Record the small TPU trace that ``tests/test_benchmark_harness.py``
reduces (run once on a chip; the file is committed).

    python3 benchmark/tools/capture_test_trace.py <out.xplane.pb>

Inside one ``bench.window`` span: three ticks, each a jitted matmul
program (device busy) and then 20 ms of host sleep inside a ``host.idle``
span (device idle).
"""

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation


def main(out: str) -> None:
    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((2048, 2048), jnp.float32)
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(d)
        with TraceAnnotation("bench.window"):
            for _ in range(3):
                with TraceAnnotation("bench.tick"):
                    f(x).block_until_ready()
                    with TraceAnnotation("host.idle"):
                        time.sleep(0.02)
        jax.profiler.stop_trace()
        src = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                        recursive=True)[0]
        shutil.copy(src, out)
    finally:
        shutil.rmtree(d)
    print(out, os.path.getsize(out), jax.devices()[0].device_kind)


if __name__ == "__main__":
    main(sys.argv[1])
