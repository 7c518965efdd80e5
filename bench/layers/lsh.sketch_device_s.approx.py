"""Device time of the SimHash sketch programs (every ``_simhash_chunk``
execution) per completed build, from the trace, in s."""
import re


def read(r):
    builds = r.extra.get("builds", 0)
    if r.trace is None or not builds:
        return None
    hit = [s for name, s in r.trace.top_programs
           if re.search(r"_simhash_chunk", name)]
    return sum(hit) / builds if hit else None
