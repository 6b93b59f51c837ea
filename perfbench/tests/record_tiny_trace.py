"""Record the small trace that ``test_trace_reduce.py`` reads, on the
chip: three jobs of one jitted program inside ``perfbench.window``, with
host sleeps between them that the reduction has to find as idle gaps.

    python3 perfbench/tests/record_tiny_trace.py <out_dir>

Prints the trace's planes and lines, and the reduction of it."""

import os
import shutil
import sys
import time


def main(out):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData, TraceAnnotation

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from trace_reduce import find_xplane, reduce_planes

    f = jax.jit(lambda x: jnp.sort(x * 3 + 1).sum())
    x = jnp.arange(1 << 20, dtype=jnp.int32)
    f(x).block_until_ready()
    tmp = out + ".tmp"
    jax.profiler.start_trace(tmp)
    with TraceAnnotation("perfbench.window"):
        for _ in range(3):
            with TraceAnnotation("job.run"):
                f(x).block_until_ready()
            with TraceAnnotation("job.release"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    path = find_xplane(tmp)
    data = ProfileData.from_file(path)
    planes = list(data.planes)
    for plane in planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  line", repr(line.name), len(evs), [
                (e.name, e.start_ns, e.duration_ns, dict(e.stats))
                for e in evs[:2]])
    print(reduce_planes(planes))
    os.makedirs(out, exist_ok=True)
    shutil.copy(path, os.path.join(out, "tiny.xplane.pb"))
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main(sys.argv[1])
