#!/usr/bin/env python3
"""How ``scoped.xplane.pb`` was recorded (PR 24, one TPU v5e chip):

    chiprun -- python3 tests/data/record_scoped_trace.py chiprun_out/scoped.xplane.pb

Half a second of a jitted step under ``jax.profiler`` the way
``analysis/profiling.py::ProfileSession`` captures: Python tracer off, host
tracer on.  The step runs two ``jax.named_scope``s (``sm_extract``,
``sm_chaos``) and one op under no scope; the host emits the ``sm_clock``
annotation at both ends and one ``sm:phase`` span annotation that carries
its own wall time, so ``tests/test_profiling.py`` can check the scope
attribution, the clock and the gap attribution against a file the TPU
profiler really wrote."""

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
        with jax.named_scope("sm_extract"):
            y = jnp.tanh(x @ x)
        with jax.named_scope("sm_chaos"):
            z = jnp.sort(y, axis=0)
        return z * 0.5 + 1.0

    x = jnp.ones((1024, 1024), jnp.float32)
    step(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with jax.profiler.TraceAnnotation("sm_clock", wall_ns=time.time_ns()):
            pass
        time.sleep(0.1)                      # idle before any span: between_jobs
        with jax.profiler.TraceAnnotation(
                "sm:phase", trace_id="t" * 16, span_id="s" * 16,
                job_id="job-1", wall_ns=time.time_ns()):
            for _ in range(3):
                x = step(x)
                x.block_until_ready()
                time.sleep(0.05)             # idle inside the span
        time.sleep(0.1)
        with jax.profiler.TraceAnnotation("sm_clock", wall_ns=time.time_ns()):
            pass
        jax.profiler.stop_trace()
        found = glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb")
        shutil.copy(found[0], out)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
