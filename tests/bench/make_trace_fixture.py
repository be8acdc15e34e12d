"""Record the small device trace the trace-reduction test reads.

  python tests/bench/make_trace_fixture.py <out.xplane.pb>

Run on one TPU chip: three ``bench.step`` spans of a jitted matmul inside
a ``bench.window`` span, with 20 ms of host sleep after each step, so the
trace holds device ops, idle gaps and the spans that name them.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out: str) -> None:
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((1024, 1024), jnp.float32)
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
                    f(x).block_until_ready()
                time.sleep(0.02)
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
