"""Device time of the programs whose trace name matches `program`, over
their runs (`per` = "run") or over one of the traffic kind's traced-window
counters (`per` = a key of `stats["traced"]`), times `scale`."""
import re


def matching(view, program: str):
    return [rec for name, rec in view["trace"]["programs"].items()
            if re.search(program, name)]


def read(view, program: str, per: str = "run", scale: float = 1.0):
    recs = matching(view, program)
    device_s = sum(r["device_s"] for r in recs)
    if per == "run":
        den = sum(r["runs"] for r in recs)
    else:
        den = view["stats"].get("traced", {}).get(per)
    if not device_s or not den:
        return None
    return scale * device_s / den
