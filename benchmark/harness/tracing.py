"""The harness's own spans and the traced slice.

``span`` records ``(name, start, end)`` on the host's clock and, while a
trace runs, writes the same span into the profiler's trace as
``bench/<name>``, on the device events' clock, where ``idle_gaps`` reads
it. ``Slice`` traces a stretch of work into a fixed directory inside the
checkout; ``read`` gives the events back and removes the files.
"""
import contextlib
import os
import shutil
import time

from . import trace_reduce


@contextlib.contextmanager
def span(name):
    import jax
    with jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + name):
        yield


class Slice:
    """``with Slice(...) as traced: <work>`` traces the work;
    ``traced.read()`` afterwards gives its events. Reading walks millions
    of host events in Python and holds the interpreter for seconds, so a
    driver calls it once the program's own threads are stopped."""

    def __init__(self, root, keep_xplane=None):
        self.dir = os.path.join(root, ".bench_trace")
        self.keep_xplane = keep_xplane
        self.t0 = self.t1 = self.read_s = None

    def __enter__(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)
        self._span = span("slice")
        self._span.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        import jax
        self.t1 = time.perf_counter()
        self._span.__exit__(*exc)
        jax.profiler.stop_trace()
        if exc[0] is not None:
            shutil.rmtree(self.dir, ignore_errors=True)
        return False

    def read(self):
        t0 = time.perf_counter()
        try:
            xplane = trace_reduce.find_xplane(self.dir)
            events = trace_reduce.read_events(xplane)
            if self.keep_xplane:
                shutil.copy(xplane, self.keep_xplane)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        self.read_s = time.perf_counter() - t0
        return events
