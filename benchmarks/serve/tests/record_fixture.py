"""Record ``fixtures/cpu_trace.xplane.pb``, the small CPU trace the
trace-reduction tests read.

    JAX_PLATFORMS=cpu python3 benchmarks/serve/tests/record_fixture.py

Inside one ``bench.window`` span: ``bench.tick`` spans that each run
one jitted program; between the first four the host sleeps 5 ms outside
any span, then comes a 30 ms ``bench.submit`` span, a tick, a 15 ms
``bench.pump`` span and a last tick.  The device does nothing in the
sleeps.  So the longest idle gap is the submit's, the next the pump's,
then three gaps outside every span; the program ran 6 times.
"""
import glob
import shutil
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

OUT = Path(__file__).resolve().parent / "fixtures" / "cpu_trace.xplane.pb"


def main():
    step = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((256, 256))
    step(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    tdir = tempfile.mkdtemp()
    jax.profiler.start_trace(tdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        def tick():
            with jax.profiler.TraceAnnotation("bench.tick"):
                step(x).block_until_ready()

        for i in range(4):
            tick()
            if i < 3:
                time.sleep(0.005)
        with jax.profiler.TraceAnnotation("bench.submit"):
            time.sleep(0.030)
        tick()
        with jax.profiler.TraceAnnotation("bench.pump"):
            time.sleep(0.015)
        tick()
    jax.profiler.stop_trace()
    src = sorted(glob.glob(f"{tdir}/plugins/profile/*/*.xplane.pb"))[-1]
    shutil.copy(src, OUT)
    shutil.rmtree(tdir)


if __name__ == "__main__":
    main()
