"""Share (%) of the matching programs' device time spent in the ops whose
trace name matches `op` (self time: a loop's body is not charged to the
loop)."""
import re

from benchmark.readers.program_time import matching


def read(view, program: str, op: str):
    recs = matching(view, program)
    total = sum(r["device_s"] for r in recs)
    if not total:
        return None
    inside = sum(v for r in recs for name, v in r["ops"].items()
                 if re.search(op, name))
    return 100.0 * inside / total if inside else None
