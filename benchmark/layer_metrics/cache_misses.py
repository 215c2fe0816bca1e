"""Programs this run compiled although it asked the persistent cache for
them: JAX's ``compile_requests_use_cache`` less ``cache_hits`` events. On a
warm cache what is left are the small programs that compile in under the
cache's threshold and are never stored."""


def read(run, name):
    ev = run.get("cache_events")
    if ev is None:
        return None
    return ev.get("/jax/compilation_cache/compile_requests_use_cache", 0) \
        - ev.get("/jax/compilation_cache/cache_hits", 0)
