"""A percentile of a host-clock sample list the traffic kind recorded
(`stats["samples"][sample]`).  Nothing recorded: no number."""
import numpy as np


def read(view, sample: str, q: float):
    values = view["stats"].get("samples", {}).get(sample) or []
    return float(np.percentile(values, q)) if len(values) else None
