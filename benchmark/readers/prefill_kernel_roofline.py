"""Share (%) of its roofline a KERNEL of the prefill programs reaches: the
least seconds the chip could take for the kernel's work on the prompts
prefilled while the profiler ran (per prompt the larger of
`<flops_fn>(sizes, prompt_tokens)` over the peak bf16 rate and
`<bytes_fn>(sizes, dtype, prompt_tokens)` over the peak bandwidth, both of
the configuration's reference module: one call a prompt, as
`prefill_mfu` counts) over the device seconds the ops named `kernel` take
in the programs matching `program` (self time, by scope path or instruction
name: `benchmark/span_reduce.py`).  `kernel_roofline` hands its counts a
decode step's rows and context, which say nothing of a prompt's length.
The prompts counted and the programs timed are the same work
(`closed_loop.ClosedLoopClient.settle_prefill`).  No such program, kernel
or prompt: nothing to read."""
import re

from benchmark import harness, span_reduce


def read(view, program: str, kernel: str, bytes_fn: str, flops_fn: str):
    r = span_reduce.of_view(view)
    lengths = view["stats"].get("traced", {}).get("prompt_lengths")
    if r is None or not lengths:
        return None
    kernel_s = sum(v for name, rec in r["programs"].items()
                   if re.search(program, name)
                   for key, v in rec["ops"].items() if re.search(kernel, key))
    if not kernel_s:
        return None
    model, peaks = view["model"], harness.peaks_of(view)
    s, dtype = model.sizes(view["config"]), view["config"]["program"]["dtype"]
    floor_s = sum(max(
        getattr(model, flops_fn)(s, n) / peaks["bf16_flops"],
        getattr(model, bytes_fn)(s, dtype, n) / peaks["hbm_bytes_per_s"])
        for n in lengths)
    return 100.0 * floor_s / (view["chips"] * kernel_s)
