"""Record the small device trace the scope-reduction tests read.

  python tests/bench/make_scoped_fixture.py <out.xplane.pb>

Run on one TPU chip. Inside one ``bench.window`` span, three
``bench.step`` spans each hold a ``repro.executor.step`` span, in which
a jitted function runs (about 20 ms of device time, so that the device
timeline's offset of about 1 ms from the host's leaves it inside its
spans) and the host then sleeps 20 ms inside a ``repro.executor.pull``
span; 5 ms of host sleep follow each step. The function's ops carry the
program's scope names: an elementwise loop under ``mfbf`` /
``relax.rung0`` and a sort under ``batch.reduce``. So the trace holds
scoped device ops, and idle gaps mostly inside a ``repro.`` span.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def scoped(x):
    with jax.named_scope("mfbf"), jax.named_scope("relax.rung0"):
        y = jax.lax.fori_loop(0, 100, lambda i, y: jnp.sin(y) + y[::-1], x)
    with jax.named_scope("batch.reduce"):
        return jnp.sort(y, axis=0)[0].sum()


def main(out: str) -> None:
    f = jax.jit(scoped)
    x = jnp.ones((4096, 4096), jnp.float32)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    log_dir = tempfile.mkdtemp(prefix="fixture-trace-")
    try:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.step"):
                    with jax.profiler.TraceAnnotation("repro.executor.step"):
                        y = f(x)
                        with jax.profiler.TraceAnnotation(
                                "repro.executor.pull"):
                            y.block_until_ready()
                            time.sleep(0.02)
                time.sleep(0.005)
        jax.profiler.stop_trace()
        path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                         recursive=True)[0]
        shutil.copyfile(path, out)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    print(f"{out}: {os.path.getsize(out)} bytes, device "
          f"{jax.devices()[0].device_kind}")


if __name__ == "__main__":
    main(sys.argv[1])
