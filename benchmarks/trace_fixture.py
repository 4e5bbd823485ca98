"""Records ``trace_fixture.xplane.pb``, the small trace the reducer is
checked on: ``python benchmarks/trace_fixture.py <out.xplane.pb>`` on a
machine with a TPU.  Twenty dispatches of one jitted program (a scan of two
matrix products and a tanh) inside the benchmark's window mark, with a
pause after every fifth so that the trace has idle gaps to find."""
import os
import shutil
import sys
import tempfile
import time


def record(out_path):
    import jax
    import jax.numpy as jnp
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks import trace_reduce

    @jax.jit
    def work(x):
        def body(c, _):
            return jnp.tanh(c @ c) @ c * 1e-3, None
        return jax.lax.scan(body, x, None, length=4)[0]

    x = jnp.ones((512, 512), jnp.bfloat16)
    work(x).block_until_ready()
    tmp = tempfile.mkdtemp(dir=os.path.dirname(os.path.abspath(out_path)))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        for i in range(20):
            with jax.profiler.TraceAnnotation("fixture.dispatch"):
                x = work(x)
            if i % 5 == 4:
                x.block_until_ready()
                with jax.profiler.TraceAnnotation("fixture.pause"):
                    time.sleep(0.002)
        x.block_until_ready()
    jax.profiler.stop_trace()
    shutil.copy(trace_reduce.find_xplane(tmp), out_path)
    shutil.rmtree(tmp)
    planes = trace_reduce.load(out_path)
    for pname, lines in planes.items():
        for lname, events in lines.items():
            print(pname, "|", lname, "|", len(events),
                  sorted({e[0] for e in events})[:12])
    print(trace_reduce.reduce(planes))


if __name__ == "__main__":
    record(sys.argv[1])
