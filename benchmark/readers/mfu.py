"""Model FLOP/s utilisation (%) of a training step, from the device: the
FLOPs a step's tokens require (`<flops_fn>` of the configuration's reference
module, times the traffic's `rows` x `seq` tokens) over the device seconds
one run of the step program takes in the trace, the chips and the peak of
`peaks.json`.  The run taken is the LONGEST traced: the profiler starts and
stops in the middle of a run, so the first and the last are cut short,
never long.  The host's clock is not in it: a host stall moves
`idle_share`, not this."""
import re

from benchmark import harness


def read(view, program: str, flops_fn: str, rows: str, seq: str):
    runs = [t for name, rec in view["trace"].get("programs", {}).items()
            if re.search(program, name) for t in rec["run_s"]]
    if not runs:
        return None
    model, tr = view["model"], view["traffic"]
    per_token = getattr(model, flops_fn)(model.sizes(view["config"]),
                                         tr[seq])
    need = per_token * tr[rows] * tr[seq]
    peak = view["chips"] * harness.peaks_of(view)["bf16_flops"]
    return 100.0 * need / (max(runs) * peak)
