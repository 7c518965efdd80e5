"""Host self time of the similarity pass per completed build: the
``similarity.pass`` span less the ``similarity.device_wait`` it spent
blocked on the chip's chunks, in s. The pass runs one chunk at a time,
so this is the time in which it leaves the chip waiting."""


def read(r):
    passes, pass_s = r.histograms.get("similarity.pass", (0, 0.0))
    _, wait_s = r.histograms.get("similarity.device_wait", (0, 0.0))
    builds = r.extra.get("builds", 0)
    return None if not passes or not builds else (pass_s - wait_s) / builds
