"""Time to write and sync one index snapshot (the ``store.save`` span)
per completed build, in s."""


def read(r):
    saves, save_s = r.histograms.get("store.save", (0, 0.0))
    builds = r.extra.get("builds", 0)
    return None if not saves or not builds else save_s / builds
