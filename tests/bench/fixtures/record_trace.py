"""Record the small profiler trace that tests/bench/test_bench_trace.py reads.

    python tests/bench/fixtures/record_trace.py OUT.xplane.pb

Run on one TPU.  Inside one ``bench.window`` annotation it runs a jitted sort
three times, each in a ``bench.step`` annotation, with a 50 ms host sleep in
a ``bench.host_wait`` annotation after each, so the trace holds device ops,
host annotations and idle gaps of known length.  Writes nothing else.
"""
import glob
import shutil
import sys
import tempfile
import time


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    f = jax.jit(lambda x: jnp.sort(x * 3 + 1))
    x = jnp.arange(1 << 20, dtype=jnp.int32)[::-1]
    f(x).block_until_ready()
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.step"):
                    f(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench.host_wait"):
                    time.sleep(0.05)
        jax.profiler.stop_trace()
        path = sorted(glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb"))[-1]
        shutil.copy(path, out)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
