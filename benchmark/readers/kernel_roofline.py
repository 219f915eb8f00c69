"""Share (%) of its roofline a KERNEL reaches inside a program: the least
seconds the chip could take for the kernel's work over a run of the program
(the larger of `<bytes_fn>` over the peak bandwidth and `<flops_fn>` over the
peak bf16 rate, both of the configuration's reference module, at the mean
rows and live context the traffic kind counted) over the device seconds the
ops named `kernel` take per run of the program in the trace (self time, by
scope path or instruction name: `benchmark/span_reduce.py`).  No such
program, kernel or counter: nothing to read."""
import re

from benchmark import harness, span_reduce
from benchmark.readers import program_time


def read(view, program: str, kernel: str, bytes_fn: str, flops_fn: str):
    r = span_reduce.of_view(view)
    c = view["stats"].get("counters", {})
    runs = sum(rec["runs"] for rec in program_time.matching(view, program))
    if r is None or not runs or not c.get("steps"):
        return None
    kernel_s = sum(v for name, rec in r["programs"].items()
                   if re.search(program, name)
                   for key, v in rec["ops"].items() if re.search(kernel, key))
    if not kernel_s:
        return None
    model, peaks = view["model"], harness.peaks_of(view)
    s = model.sizes(view["config"])
    rows = c["rows"] / c["steps"]
    context = c["context_tokens"] / c["steps"]
    floor_s = max(
        getattr(model, bytes_fn)(s, view["config"]["program"]["dtype"],
                                 rows=rows, context_tokens=context)
        / peaks["hbm_bytes_per_s"],
        getattr(model, flops_fn)(s, rows=rows, context_tokens=context)
        / peaks["bf16_flops"])
    return 100.0 * floor_s / (kernel_s / runs)
