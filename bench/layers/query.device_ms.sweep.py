"""Device time of one execution of the batched query program
(``query_batch``) from the trace, in ms."""


def read(r):
    got = r.trace.device_time("query_batch")
    return None if got is None else 1e3 * got[0] / got[1]
