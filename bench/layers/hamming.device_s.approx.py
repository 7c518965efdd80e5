"""Device time of the sketch comparison program (``hamming_distance``,
the Pallas ``hamming`` kernel and its operand layout) per completed
build, from the trace, in s."""
import re


def read(r):
    builds = r.extra.get("builds", 0)
    if r.trace is None or not builds:
        return None
    hit = [s for name, s in r.trace.top_programs
           if re.search(r"hamming_distance", name)]
    return sum(hit) / builds if hit else None
