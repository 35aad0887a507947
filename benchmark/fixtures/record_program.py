"""Records ``v5e_program.xplane.pb``, the fixture of
`benchmark/reduce/program.py`, on a TPU: ``python3
benchmark/fixtures/record_program.py <out_dir>``. Three rounds of a
small program (one fusion under a ``zoo:`` scope, one under none)
run six times a round, the host before each run but the first in another state:
under a ``zoo:`` span (``fixture/input``), under no span at all,
under a ``*_wait`` span that a working span on another thread
overlaps (``fixture/place``), under a ``*_wait`` span alone, and
dispatching from inside a span (``fixture/step``)."""

import glob
import os
import shutil
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax                                          # noqa: E402
import jax.numpy as jnp                             # noqa: E402

import analytics_zoo_tpu                            # noqa: E402,F401
from analytics_zoo_tpu.common import tracing        # noqa: E402


@jax.jit
def step(x, w):
    with jax.named_scope("zoo:fixture/layer"):
        with jax.named_scope("zoo:fixture/matmul"):
            y = jnp.tanh(x @ w)
    return (y * 0.5) @ w                       # under no scope


def main(out_dir: str):
    if jax.devices()[0].platform != "tpu":
        sys.exit("the fixture is recorded on a TPU")
    x = jnp.ones((2048, 1024), jnp.bfloat16)
    w = jnp.full((1024, 1024), 0.01, jnp.bfloat16)
    def run():
        return step(x, w).block_until_ready()

    def place():
        with tracing.annotate("fixture/place"):
            time.sleep(0.010)

    run()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    trace_dir = os.path.join(out_dir, "trace")
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    for i in range(3):
        run()
        with tracing.annotate("fixture/input"):
            time.sleep(0.012)
        run()
        time.sleep(0.008)
        run()
        worker = threading.Thread(target=place)
        with tracing.annotate("fixture/data_wait"):
            worker.start()
            worker.join()
        run()
        with tracing.annotate("fixture/data_wait"):
            time.sleep(0.008)
        run()
        with tracing.trace("fixture/step", step=i):
            run()
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    shutil.copy(found[-1], os.path.join(out_dir,
                                        "v5e_program.xplane.pb"))
    shutil.rmtree(trace_dir)


if __name__ == "__main__":
    main(sys.argv[1])
