"""Device idle time (ms per step) under the program's own host spans whose
name matches `spans`: each idle gap of the traced window is charged to the
innermost `serve.*` / `engine.*` / `train.*` span over it (`_outside_`:
under none), and the sum is taken over the step spans wholly inside the
window (`benchmark/span_reduce.py`).  A program that emits none of the
named spans, or no step span, gives nothing to read."""
import re

from benchmark import span_reduce


def read(view, spans: str):
    r = span_reduce.of_view(view)
    if r is None or not r["steps"]:
        return None
    known = set(r["seen"]) | {span_reduce.OUTSIDE}
    if not any(re.search(spans, name) for name in known):
        return None
    idle_s = sum(v for name, v in r["idle_s"].items()
                 if re.search(spans, name))
    return 1e3 * idle_s / r["steps"]
