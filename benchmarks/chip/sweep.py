"""Find a configuration's knee: the highest offered rate it sustains.

    python3 benchmarks/chip/sweep.py --workload <cell> --seconds 10 --seeds 2 --pps 50000 100000 ...

Runs the cell once per rate and seed, each in its own process (one process
holds the chip at a time), and prints one line per run: the generator's
lateness p50 and p99, packets never ingested, and table drops. A run
sustains its rate when the generator keeps up: its median lateness stays
under ``--late-ms``, under 0.1% of the window's packets are left
undelivered at its end, nothing is dropped and the run is correct. (Its
99th percentile swings by tens of ms at every rate, so it does not judge.)
The knee is the highest rate that every seed sustains, with every lower
rate sustained too. The sweep is run once, when a cell is defined; its
rate goes into the configuration's ``knee_pps``.
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
from pathlib import Path

RUN = Path(__file__).resolve().parent / "run.py"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--late-ms", type=float, default=2.0)
    ap.add_argument("--pps", type=float, nargs="+", required=True)
    a = ap.parse_args()
    knee, holding = None, True
    for k, pps in enumerate(sorted(a.pps)):
        held = True
        for j in range(a.seeds):
            p = subprocess.run(
                [sys.executable, str(RUN), "--workload", a.workload, "--seed",
                 str(a.seed + 1000 * k + j), "--seconds", str(a.seconds),
                 "--trace", "0", "--pps", str(pps)], capture_output=True, text=True)
            if p.returncode != 0 or not p.stdout.strip():
                print(f"pps {pps:.0f}: run failed rc={p.returncode}\n{p.stderr[-2000:]}")
                held = False
                continue
            out = json.loads(p.stdout.strip().splitlines()[-1])
            t = out["tap"]
            ok = (t["lateness_p50_ms"] is not None and t["lateness_p50_ms"] < a.late_ms
                  and t["never_ingested"] < 1e-3 * t["due_pps"] * a.seconds
                  and t["drops"] == 0 and out["correct"])
            held &= ok
            print(json.dumps({"pps": pps, "seed": a.seed + 1000 * k + j, "sustained": ok,
                              "correct": out["correct"],
                              "metrics": {m: v["value"] for m, v in out["metrics"].items()},
                              **t}), flush=True)
        holding &= held
        if holding:
            knee = pps
    print(json.dumps({"workload": a.workload, "knee_pps": knee}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
