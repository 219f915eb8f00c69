"""Share (%) of the traced window in which no op ran on the device."""


def read(view):
    t = view["trace"]
    if not t.get("window_s") or not t.get("busy_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
