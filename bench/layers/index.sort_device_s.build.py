"""Device time of the index orders program (``_build_orders``: the NO and
CO sorts) per completed build, from the trace, in s."""


def read(r):
    got = r.trace.device_time("sorts")
    builds = r.extra.get("builds", 0)
    return None if got is None or not builds else got[0] / builds
