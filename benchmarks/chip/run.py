"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``. The last line of standard output
is the result: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and with ``--trace 1`` ``breakdown``), then ``checks``, each
number compared with its limit; the same numbers are the last lines of
standard error. Without a TPU, or with fewer chips than the cell needs, the
run prints no result and exits 3.

Options for defining the cell, not for its runs: ``--pps`` offers that rate
instead of the mix's (the knee sweep), ``--control 1`` also prints the
bfloat16 control's numbers, ``--keep-trace PATH`` writes the start of the
traced window as a test fixture.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--pps", type=float, default=None)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None)
    a = ap.parse_args(argv)
    if not (harness.ROOT / "src" / "repro").is_dir():
        print("run.py: the program under test (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    try:
        out, _ = harness.run(a.workload, a.seed, a.seconds, bool(a.trace),
                             pps=a.pps, control=bool(a.control), keep_trace=a.keep_trace,
                             log=lambda s: print(s, file=sys.stderr, flush=True))
    except harness.NoChip as e:
        print(f"run.py: {e}; no result", file=sys.stderr)
        return 3
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
