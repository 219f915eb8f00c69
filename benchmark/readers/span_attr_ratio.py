"""One attribute of the program's host spans named `span` over another,
both summed over the spans the trace holds: `scale * sum(num) / (sum(den) *
config[den_times])`.  The attributes are the annotation's stats in the
xplane file (`utils.spans.span(name, **attrs)` / `set_metadata`).  A
program that writes no such span or attribute gives nothing to read."""
import functools
import os

from benchmark import span_reduce


@functools.lru_cache(maxsize=4)
def attributes(path: str, span: str):
    """The stats of every host event named `span`, one dict each (a run's
    file is read once for all the metrics that ask)."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if span_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            out += [dict(e.stats) for e in line.events
                    if span_reduce.base_name(e.name) == span]
    return out


def read(view, span: str, num: str, den: str, scale: float = 1.0,
         den_times: str = None):
    if not view.get("trace"):
        return None
    path = span_reduce.newest_xplane(os.path.join(
        os.path.dirname(view["bench_dir"]), ".cache", "bench_trace"))
    if path is None:
        return None
    try:
        spans = attributes(path, span)
        top = sum(float(a[num]) for a in spans)
        bottom = sum(float(a[den]) for a in spans)
    except (KeyError, TypeError, ValueError):
        return None
    if den_times is not None:
        bottom *= view["config"][den_times]
    return scale * top / bottom if bottom else None
