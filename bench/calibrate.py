"""The readings that a cell's limits are set from, on the chip, in one
process:

    python3 bench/calibrate.py --workload <cell> --seeds <n> [--seconds <s>]

For each seed it makes a whole run of the cell as ``run.py`` does (set-up,
the window at the cell's own load, the check), and then puts the control in
the program's place on the same inputs and checks it with the same
comparison: for a served model the int8 reference's first token at each
served position, for the solver the reference in bfloat16. It prints one
JSON line per seed, with each number the program and the control read and
whether each passed; the control has to come out not correct. The
benchmark's own runs never run the control.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=3000000000)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args()
    cell = run.prepare(args.workload)
    for i in range(args.seeds):
        seed = args.first_seed + i
        t0 = time.perf_counter()
        rec = run.execute(cell, seed, args.seconds, False, t0, control=True)
        row = {"seed": seed, "seconds": round(time.perf_counter() - t0, 1)}
        for side, checks in (("program", rec["checks"]),
                             ("control", rec["control"])):
            row[side] = {k: c["value"] for k, c in checks.items()}
            row[side + "_correct"] = all(c["ok"] for c in checks.values())
        row["program_correct"] &= rec["failed"] == 0
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
