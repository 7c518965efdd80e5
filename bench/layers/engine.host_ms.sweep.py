"""Host time of one engine flush (the ``engine.flush`` span less the
``engine.device_wait`` it spent blocked on the batched query), in ms:
bucketing, cache lookups, dispatch and the host copies of the answers."""


def read(r):
    flushes, flush_s = r.histograms.get("engine.flush", (0, 0.0))
    _, wait_s = r.histograms.get("engine.device_wait", (0, 0.0))
    return None if not flushes else 1e3 * (flush_s - wait_s) / flushes
