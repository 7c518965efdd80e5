"""Mean host-clock time of one engine device call for global requests
(the ``engine.device_call`` span: the batched query through the host copy
of its answer), in ms."""


def read(r):
    mean = r.mean("engine.device_call")
    return None if mean is None else 1e3 * mean
