"""The control of a cell's `correct`, and the readings its limits are
set from: for each seed, one short run of the cell (the program's
numbers) and the CONTROL's numbers on the same inputs, all in one
process so that set-up is paid once a seed and compiles once.

    python -m chipbench.control --workload <name> --seeds 1,2,3 --seconds <s>

Both go through the harness's own comparison (`Context.result`): the
run's outcome as it is, and a copy of it in which each number the
control reads has taken the program's place. The program has to come
out `correct`, the control not: exit code 1 otherwise. `--dump <dir>`
keeps each seed's raw readings (`<workload>.<seed>.npz`) for setting a
limit.

Not part of a check: the driver never runs it. One JSON line a seed.
"""

import argparse
import json
import os
import sys
import time


def swapped(outcome: dict, control_checks: dict) -> dict:
    """The outcome with the control's numbers in the program's place."""
    return dict(outcome, checks=[(n, control_checks.get(n, v), lim)
                                 for n, v, lim in outcome["checks"]])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", default=None)
    ap.add_argument("--dump", default=None)
    args = ap.parse_args(argv)
    from chipbench import harness
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            ctx = harness.Context(args.workload, seed, args.seconds, False,
                                  time.perf_counter(), rehearse=args.rehearse)
            ctx.require_device()
            driver = ctx.driver()
        except harness.Refused as e:
            print(f"chipbench: refused: {e}", file=sys.stderr)
            return 2
        outcome = driver.run(ctx)
        control = driver.control(ctx, outcome)
        program_line = ctx.result(outcome)
        control_line = ctx.result(swapped(outcome, control["checks"]))
        if args.dump:
            import numpy as np
            os.makedirs(args.dump, exist_ok=True)
            raw = {"program_" + k: v
                   for k, v in (outcome.get("raw") or {}).items()}
            raw.update({"control_" + k: v
                        for k, v in control.get("raw", {}).items()})
            np.savez(os.path.join(
                args.dump, f"{args.workload}.{seed}.npz"), **raw)
        row = {"seed": seed,
               "program_correct": program_line["correct"],
               "control_correct": control_line["correct"],
               "program": program_line["checks"],
               "control": {n: control_line["checks"][n]
                           for n in control["checks"]},
               "control_numbers": control.get("numbers"),
               "end_to_end": outcome["end_to_end"]}
        del outcome, control
        rows.append(row)
        print(json.dumps(row), flush=True)
    names = sorted(rows[0]["control"])
    ok = all(r["program_correct"] and not r["control_correct"] for r in rows)
    print(json.dumps({"summary": {
        n: {"program_max": max(r["program"][n]["value"] for r in rows),
            "control_min": min(r["control"][n]["value"] for r in rows),
            "limit": rows[0]["program"][n]["limit"]}
        for n in names},
        "every_program_correct_and_every_control_not": ok}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
