"""Device time of the similarity kernel (``bucket_probe``, the Pallas
probe of the compiled lane) per completed build, from the trace, in s."""


def read(r):
    got = r.trace.kernel_time("bucket_probe")
    builds = r.extra.get("builds", 0)
    return None if got is None or not builds else got[0] / builds
