"""Share of global requests the result cache answered in the window
(``engine.cache_hits`` over ``engine.requests``), in %."""


def read(r):
    requests = r.counter("engine.requests")
    if not requests:
        return None
    return 100.0 * r.counter("engine.cache_hits") / requests
