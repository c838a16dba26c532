#!/usr/bin/env python3
"""Record the small trace that ``test_trace.py`` reduces.

    python3 bench/tests/record_trace.py OUT_DIR

On a TPU: five calls of one small jitted program inside the harness's
window span, each followed by 20 ms of host work in a span named
``host_work``, so the device is idle for about 100 ms of the window and the
reduction must attribute that idle time to ``host_work``.
"""

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

CALLS = 5
HOST_WORK_S = 0.02


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    from harness.trace import WINDOW

    step = jax.jit(lambda x: jnp.tanh(x @ x) * 0.5)
    x = jnp.ones((2048, 2048), jnp.float32)
    step(x).block_until_ready()
    jax.profiler.start_trace(out_dir)
    with jax.profiler.TraceAnnotation(WINDOW):
        for _ in range(CALLS):
            x = step(x).block_until_ready()
            with jax.profiler.TraceAnnotation("host_work"):
                time.sleep(HOST_WORK_S)
    jax.profiler.stop_trace()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
