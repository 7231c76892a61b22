"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of `BENCHMARK.json` on the chips of this machine and prints,
as the last line of standard output, one JSON object with `correct`,
`attempted`, `failed`, `metrics` and `device`. Without a TPU of a kind in
`benchmark/peaks.json` it exits 3 and prints no result.
"""

from __future__ import annotations

import time

_T0 = time.time()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmark.lib import harness  # noqa: E402


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    try:
        if importlib.util.find_spec("imaginaire_tpu") is None:
            raise ImportError("the system under test, imaginaire_tpu, is "
                              "not in this directory")
        loaded, peaks, devices = harness.start(args.workload)
    except (OSError, ImportError, KeyError, harness.BenchmarkError) as e:
        print(f"benchmark: cannot load cell {args.workload!r}: {e}",
              file=sys.stderr)
        return 2
    driver = harness.load_driver(loaded["workload"]["driver"])
    run = driver.run(loaded, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), devices=devices, peaks=peaks,
                     clock=harness.Clock(_T0))
    harness.emit(**run)
    return 0


if __name__ == "__main__":
    sys.exit(main())
