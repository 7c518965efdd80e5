"""Propagation rounds one batched query call ran (``query.rounds`` over
``engine.device_queries``): the batched loop runs until its slowest lane
converges."""


def read(r):
    rounds = r.counter("query.rounds")
    calls = r.counter("engine.device_queries")
    return None if not rounds or not calls else rounds / calls
