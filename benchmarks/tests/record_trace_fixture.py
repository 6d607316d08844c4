"""Record the small device trace the trace-reduction test reads.

    python3 benchmarks/tests/record_trace_fixture.py <out.xplane.pb>

Run it on a machine with a TPU. Inside a ``bench.window`` annotation it
runs one small jitted program three times, with host sleeps between
the runs annotated ``bench.enqueue``, so the trace has device work,
idle gaps and host events that label them.
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
    if jax.devices()[0].platform != "tpu":
        sys.exit("record the fixture on a TPU")
    f = jax.jit(lambda x: jnp.sum(jnp.sin(x) @ x))
    x = jnp.ones((1024, 1024), jnp.float32)
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.enqueue"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, out)
    shutil.rmtree(d)
    print(out, os.path.getsize(out))


if __name__ == "__main__":
    main(sys.argv[1])
