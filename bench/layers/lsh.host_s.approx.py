"""Host time of the approximate similarity pass per completed build:
the ``lsh.sketch``, ``lsh.compare``, ``lsh.exact_subset`` and
``lsh.merge`` spans less the waits on the device inside them (the
``lsh.device_wait`` events, and the exact subset's own
``similarity.device_wait``), in s. A program without those spans gives
nothing."""

SPANS = ("lsh.sketch", "lsh.compare", "lsh.exact_subset", "lsh.merge")
WAITS = ("lsh.device_wait", "similarity.device_wait")


def read(r):
    builds = r.extra.get("builds", 0)
    if not builds or "lsh.sketch" not in r.histograms:
        return None
    spans = sum(r.histograms.get(s, (0, 0.0))[1] for s in SPANS)
    waits = sum(r.histograms.get(w, (0, 0.0))[1] for w in WAITS)
    return (spans - waits) / builds
