"""One of the traffic kind's counters over another (or alone), scaled:
`stats["counters"][num] / stats["counters"][den] * scale`."""


def read(view, num: str, den: str = None, scale: float = 1.0):
    counters = view["stats"].get("counters", {})
    if num not in counters or (den is not None and not counters.get(den)):
        return None
    return scale * counters[num] / (counters[den] if den else 1.0)
