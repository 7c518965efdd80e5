"""Share of the similarity pass's half-edges given their twin's σ instead
of a chunk program's (``similarity.sigma_twinned`` over
``similarity.sigma_computed`` + ``similarity.sigma_twinned``), in %: 50
where σ is computed once per undirected edge, 0 where every half-edge is
computed. A program without those counters gives nothing."""


def read(r):
    if "similarity.sigma_twinned" not in r.counters:
        return None
    twinned = r.counter("similarity.sigma_twinned")
    done = r.counter("similarity.sigma_computed") + twinned
    return None if not done else 100.0 * twinned / done
