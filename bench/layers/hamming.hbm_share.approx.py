"""Share of the HBM roofline that the ``hamming_distance`` program
reaches, in %: the bytes the comparison must move per build (both
endpoints' packed sketches read and one int32 distance written, for each
of the m2 half-edges, whatever block the program pads them to) over the
program's device time per build and the device's
published HBM bandwidth (``bench/peaks.json``, keyed by device kind; a
kind not in it is an error). The program's time includes its operand
transposes, so the share is of the program, never above the kernel's."""
import json
import re
from pathlib import Path

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


def hamming_bytes(m2: int, words: int) -> int:
    """Bytes one comparison of m2 half-edges must move: 2 · m2 · words ·
    4 read (the uint32 sketches at both endpoints), m2 · 4 written
    (int32)."""
    return 2 * m2 * words * 4 + m2 * 4


def hbm_bytes_per_s(device_kind: str) -> float:
    with open(PEAKS) as f:
        peaks = json.load(f)
    if device_kind not in peaks:
        raise KeyError(f"no HBM peak for device kind {device_kind!r} "
                       f"in {PEAKS}")
    return float(peaks[device_kind]["hbm_bytes_per_s"])


def read(r):
    builds = r.extra.get("builds", 0)
    m2, words = r.extra.get("m2"), r.extra.get("sketch_words")
    if r.trace is None or not builds or not m2 or not words:
        return None
    secs = sum(s for name, s in r.trace.top_programs
               if re.search(r"hamming_distance", name))
    if not secs:
        return None
    return (100.0 * hamming_bytes(m2, words) * builds
            / (secs * hbm_bytes_per_s(r.device_kind)))
