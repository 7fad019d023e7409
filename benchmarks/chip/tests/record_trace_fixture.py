"""Record the small profiler trace that the trace-reduction test reads.

    python3 benchmarks/chip/tests/record_trace_fixture.py <out.json.gz>

Run on a TPU host: a few steps of a matrix product and one call of the
program's ``blockhash`` kernel (on 64 MiB), inside the harness's ``window``, ``step``
and ``ctx.store`` annotations and with the harness's profiler options,
so the trace has the same planes, lines and names as a benchmark run.
"""
from __future__ import annotations

import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent.parent.parent / "src"))
sys.path.insert(0, str(HERE.parent))


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp

    import trace_reduce
    from repro.kernels import ops

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    step = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((4096, 4096), jnp.bfloat16)
    leaf = jnp.arange(1 << 24, dtype=jnp.float32)
    step(x).block_until_ready()
    ops.blockhash(leaf).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, create_perfetto_trace=True, profiler_options=options)
        with jax.profiler.TraceAnnotation("window"):
            for _ in range(20):
                with jax.profiler.TraceAnnotation("step"):
                    x = step(x)
            with jax.profiler.TraceAnnotation("ctx.store"):
                ops.blockhash(leaf).block_until_ready()
            x.block_until_ready()
        jax.profiler.stop_trace()
        shutil.copy(trace_reduce.trace_file(Path(d)), out)
    print(trace_reduce.reduce_events(trace_reduce.load_events(Path(out))))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
