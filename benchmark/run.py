"""Run one cell of BENCHMARK.json once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result (`harness.emit`); the
compared numbers, each beside its limit, are the last lines of standard
error. No TPU, too few chips, an unknown cell or device kind, or any
crash: exit nonzero and print no result."""

import time

T_START = time.perf_counter()  # set-up is timed from the process's start

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="put the lower-precision reference in the "
                         "program's place in the check, which must then "
                         "fail (the benchmark's own runs leave it off)")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's xplane.pb to this path")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark import harness, spec

    cell = spec.load_cell(args.workload)
    spec.use_compile_cache()
    log = harness.CompileLog().register()
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START,
                              control=bool(args.control),
                              keep_trace=args.keep_trace, compile_log=log)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception:  # no result line: exit nonzero
        traceback.print_exc()
        sys.exit(1)
