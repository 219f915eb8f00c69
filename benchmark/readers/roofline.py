"""Share (%) of the HBM roofline a program reaches: the seconds its bytes
need at the chip's peak bandwidth over the device seconds a run of it takes.
The bytes come from `<bytes_fn>` of the configuration's reference module at
the mean rows and live context the traffic kind counted; the peak from
`peaks.json`."""
from benchmark import harness
from benchmark.readers import program_time


def read(view, program: str, bytes_fn: str):
    per_run = program_time.read(view, program)
    c = view["stats"].get("counters", {})
    if per_run is None or not c.get("steps"):
        return None
    peaks = harness.peaks_of(view)
    model = view["model"]
    need = getattr(model, bytes_fn)(
        model.sizes(view["config"]),
        view["config"]["program"]["dtype"],
        rows=c["rows"] / c["steps"],
        context_tokens=c["context_tokens"] / c["steps"])
    floor_s = need / peaks["hbm_bytes_per_s"]
    return 100.0 * floor_s / per_run
