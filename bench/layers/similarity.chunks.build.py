"""Chunk programs the similarity pass dispatched per completed build
(the ``similarity.chunks`` counter), each a synchronous round trip."""


def read(r):
    chunks = r.counter("similarity.chunks")
    builds = r.extra.get("builds", 0)
    return None if not chunks or not builds else chunks / builds
