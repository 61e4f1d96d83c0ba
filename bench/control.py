"""Readings that set a cell's correctness limit (not run by the
benchmark's own runs).

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

For each seed, one run of the cell at its own sizes and load
(``run.run_cell`` with ``control``), all in one process so that the
set-up compiles once.  The float32 reference is read twice over the
requests sampled from the window: against the served tokens (the
program's reading) and against the fp8 control's first choices at the
same positions, which ``run.judge`` then judges in the served tokens'
place.  Set-up does not replay the traffic (``warm=False``): the window
may build programs, which moves no token, so that check is left out
of both readings.  One JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    try:
        devs = run.check_devices(cell.chips)
        peaks = run.load_peaks(devs[0].device_kind)
    except run.NoChip as e:
        run.log(f"control: {e}; nothing run")
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run_cell(cell, seed, args.seconds, False, peaks,
                           t_start=run.time.monotonic(), control=True,
                           warm=False)
        program = res["program_checks"]
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "program_correct": run.judge(
                cell.limits, res["failed"], None,
                program["sampled_tokens"]["value"],
                program["max_logit_gap"]["value"])[0],
            "program": program, "control_correct": res["correct"],
            "control": res["checks"], "metrics": res["metrics"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
