"""Backend compiles and persistent-cache traffic over a ``with`` block
(a copy of ``chip_smoke.CompileWatch``, plus the count of compile events:
the measured window must hold none)."""


class CompileWatch:

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name, seconds, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds
            self.compiles += 1

    def __enter__(self):
        import jax.monitoring as m
        m.register_event_listener(self._event)
        m.register_event_duration_secs_listener(self._duration)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as m
        m.unregister_event_listener(self._event)
        m.unregister_event_duration_listener(self._duration)

    @property
    def quiet(self):
        """Nothing was compiled and the cache was not asked."""
        return not (self.compiles or self.hits or self.misses)

    def fields(self):
        return {"compile_seconds": self.seconds, "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}
