"""One run of one cell:

    python -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Fails (exit 2, no result line) without a TPU: there is no CPU fallback.
The last line of standard output is the contract's one JSON object; the
numbers that decided `correct` are also the last lines of standard
error, each beside its limit.
"""

import time

T_START = time.perf_counter()      # set-up is counted from here

import argparse  # noqa: E402
import json      # noqa: E402
import sys       # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", default=None,
                    help="JSON of size overrides for a CPU rehearsal; "
                         "refused on a TPU")
    args = ap.parse_args(argv)

    from chipbench import harness
    try:
        ctx = harness.Context(args.workload, args.seed, args.seconds,
                              bool(args.trace), T_START,
                              rehearse=args.rehearse)
        ctx.require_device()
        driver = ctx.driver()
    except harness.Refused as e:
        print(f"chipbench: refused: {e}", file=sys.stderr)
        return 2
    try:
        outcome = driver.run(ctx)
    finally:
        ctx.trace_abort()
    line = ctx.result(outcome)
    sys.stdout.flush()
    for name, c in line["checks"].items():
        print(f"check {name}: value {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
