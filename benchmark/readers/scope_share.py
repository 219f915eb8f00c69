"""Share (%) of the matching programs' device time spent in the ops under
a scope or kernel name matching `scope` (self time).  An op is known by the
scope path of its HLO `op_name` (the `jax.named_scope`s of the program, the
transformations around them) and by its instruction name (a
`pallas_call`'s `name=`): `benchmark/span_reduce.py`.  No such program in
the trace, or no op under the scope: nothing to read."""
import re

from benchmark import span_reduce


def read(view, program: str, scope: str):
    r = span_reduce.of_view(view)
    if r is None:
        return None
    recs = [rec for name, rec in r["programs"].items()
            if re.search(program, name)]
    total = sum(rec["device_s"] for rec in recs)
    inside = sum(v for rec in recs for key, v in rec["ops"].items()
                 if re.search(scope, key))
    return 100.0 * inside / total if total and inside else None
