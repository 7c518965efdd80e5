"""Share of the lockstep lane-rounds of the batched query calls that a
lane still needed (``query.lane_rounds`` over slots per call times
``query.rounds``), in %. The rest ran on lanes that had converged.

Slots per call is the engine's ``max_batch``: every call is padded to
it, and the benchmark's service runs the default ``EngineConfig``."""


def read(r):
    rounds = r.counter("query.rounds")
    if not rounds:
        return None
    from repro.serve import EngineConfig
    slots = EngineConfig().max_batch
    return 100.0 * r.counter("query.lane_rounds") / (slots * rounds)
