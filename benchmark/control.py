"""python3 -m benchmark.control --workload <name> --seconds <s> --seeds <n> [<n> ...] [--control int8]

Where the limits of `correct` come from: the numbers compared, each beside
its limit, on the chip at the cell's own size, several seeds in one process
(set-up is long).  Each seed goes through `harness.run_cell` as the
benchmark runs it and has to come out correct.  With `--control` it goes
through once more with the reference, computed in that lower precision, in
the program's place, and that run has to come out NOT correct.  One line a
run; exit code 0 when all came out as they must.  Not part of a benchmark
run (PERF.md section 6 holds the readings).
"""
import argparse
import json
import os
import sys

from benchmark import harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m benchmark.control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", default=None)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from deepspeed_tpu.utils.device import place_compile_cache
    place_compile_cache()
    cell = harness.load_cell(harness.ROOT, harness.BENCH_DIR, args.workload)
    devices = harness.require_chips(cell["chips"])
    as_expected = True
    for seed in args.seeds:
        for control in [None] + [args.control] * bool(args.control):
            res = harness.run_cell(cell, seed, args.seconds, False,
                                   devices=devices, control=control)
            as_expected &= res["correct"] == (control is None)
            print(json.dumps({
                "workload": args.workload, "seed": seed, "control": control,
                "correct": res["correct"], "compared": res["compared"],
                "notes": {k: v for k, v in res["notes"].items()
                          if k != "warmed"}}, default=str), flush=True)
    print(f"[control] sound runs correct and controls not: {as_expected}",
          file=sys.stderr)
    return 0 if as_expected else 1


if __name__ == "__main__":
    raise SystemExit(main())
