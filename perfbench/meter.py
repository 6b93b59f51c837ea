"""Compile accounting from JAX's own ``jax.monitoring`` events, copied from
``chip_smoke.py:CompileMeter``.  ``backend_compile_duration`` wraps
``compile_or_get_cached``, so a program loaded from the persistent cache
counts as a compile here too: both cost time inside a window."""


class CompileMeter(object):
    """Counts backend compiles (persistent-cache retrievals included),
    their seconds, and persistent-cache hits."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.seconds = 0.0
        self.hits = 0
        self.by_name = {}  # program name -> [compiles, seconds]
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, event, secs, fun_name="?", **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += secs
            entry = self.by_name.setdefault(fun_name, [0, 0.0])
            entry[0] += 1
            entry[1] += secs

    def top(self, k=8):
        ranked = sorted(self.by_name.items(), key=lambda kv: -kv[1][1])
        return ", ".join("{} x{} {:.3f}s".format(name, n, secs)
                         for name, (n, secs) in ranked[:k])

    def snapshot(self):
        return (self.compiles, self.seconds, self.hits)
