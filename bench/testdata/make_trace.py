"""Record ``cpu_trace.xplane.pb``: a small profiler trace on the CPU whose
intervals are known, for ``tests/test_trace_reduce.py``.

    JAX_PLATFORMS=cpu python3 bench/testdata/make_trace.py

Inside a ``bench.window`` annotation: three 30 ms ``dev.prox_pool`` spans
and two 20 ms ``dev.fusion`` spans stand for device operations, separated
by 50 ms ``host.wait`` spans (the idle gaps) and one 80 ms
``host.gather``.  The test reads the ``dev.`` spans as the device's
operations.
"""

import glob
import os
import shutil
import tempfile
import time

import jax

HERE = os.path.dirname(os.path.abspath(__file__))
PLAN = [("dev.prox_pool", 0.030), ("host.wait", 0.050),
        ("dev.fusion", 0.020), ("host.wait", 0.050),
        ("dev.prox_pool", 0.030), ("host.gather", 0.080),
        ("dev.prox_pool", 0.030), ("host.wait", 0.050),
        ("dev.fusion", 0.020)]


def main():
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for name, secs in PLAN:
            with jax.profiler.TraceAnnotation(name):
                time.sleep(secs)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, os.path.join(HERE, "cpu_trace.xplane.pb"))
    shutil.rmtree(d)


if __name__ == "__main__":
    main()
