"""Model FLOP/s utilisation (%) of the prefill programs, from the device:
the FLOPs the forward pass of each prompt prefilled while the profiler ran
needs (`<flops_fn>(sizes, prompt_tokens)` of the configuration's reference
module, one call a prompt: attention grows with the square of a prompt's
length, so a sum of lengths is not enough) over the device seconds the
programs matching `program` took in the trace, the chips and the peak of
`peaks.json`.  The whole step a first token waits for against the chip's
peak: a change that takes a kernel off that path leaves the kernel's own
roofline silent and still has to show here.  A prompt counts when its first
token falls in the traced stretch, and a closed-loop run begins and ends
that stretch where no prompt is being prefilled
(`closed_loop.ClosedLoopClient.settle_prefill`), so the prompts counted and
the programs timed are the same work, with no program cut at either end.
No such program or no prompt: nothing to read."""
from benchmark import harness
from benchmark.readers import program_time


def read(view, program: str, flops_fn: str):
    lengths = view["stats"].get("traced", {}).get("prompt_lengths")
    device_s = sum(r["device_s"]
                   for r in program_time.matching(view, program))
    if not lengths or not device_s:
        return None
    model = view["model"]
    s = model.sizes(view["config"])
    need = sum(getattr(model, flops_fn)(s, n) for n in lengths)
    peak = view["chips"] * harness.peaks_of(view)["bf16_flops"]
    return 100.0 * need / (device_s * peak)
