"""Device time of the similarity pass (every group chunk program) per
completed build, from the trace, in s."""


def read(r):
    got = r.trace.device_time("sigma")
    builds = r.extra.get("builds", 0)
    return None if got is None or not builds else got[0] / builds
