"""Share of the similarity pass's chunk programs dispatched while the
previous chunk was still on the device (``similarity.chunks_queued`` over
``similarity.chunks``), in %: how often the pass finds the chip's queue
fed rather than drained. A program without that counter gives nothing."""


def read(r):
    chunks = r.counter("similarity.chunks")
    if not chunks or "similarity.chunks_queued" not in r.counters:
        return None
    return 100.0 * r.counter("similarity.chunks_queued") / chunks
