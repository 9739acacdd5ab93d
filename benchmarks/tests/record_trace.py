#!/usr/bin/env python3
"""How ``data/small.xplane.pb`` was recorded (PR 23, one TPU v5e chip):

    chiprun -- python3 benchmarks/tests/record_trace.py chiprun_out/small.xplane.pb

A fraction of a second of a jitted loop under ``jax.profiler`` with the host
and Python tracers off, so the file holds the device planes as the TPU
profiler writes them (``/device:TPU:0`` with ``XLA Modules`` and ``XLA Ops``)
and little else.  The rehearsal reduces it in place of a run's own capture."""

import glob
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out: str) -> int:
    assert jax.devices()[0].platform == "tpu", jax.devices()

    @jax.jit
    def step(x):
        return jnp.tanh(x @ x) * 0.5 + jnp.sort(x, axis=0)

    x = jnp.ones((1024, 1024), jnp.float32)
    step(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        for _ in range(4):
            x = step(x)
            x.block_until_ready()
            time.sleep(0.05)
        jax.profiler.stop_trace()
        found = glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb")
        shutil.copy(found[0], out)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
