"""python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run of one cell, in a new process, on the TPU this machine holds (it
fails anywhere else).  The last line of standard output is the result
object; the numbers compared with the reference, each beside its limit, are
the last lines of standard error.
"""
import os
import sys
import time

# set-up is counted from the start of the first process: a configuration
# that states process environment has the run started again with it
T_START = float(os.environ.get("_BENCH_T_START") or time.time())

from benchmark import harness          # noqa: E402

if __name__ == "__main__":
    raise SystemExit(harness.main(sys.argv[1:], T_START))
