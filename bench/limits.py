"""The readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/limits.py --workload <cell> --seeds 12 --seconds 5

In one process, runs the cell on ``--seeds`` seeds as the program stands,
then on three seeds each with the consumer's bfloat16 control and with each
fault the cell can have planted under the timed path (a step that returns
its state unchanged reads 1 on ``change3_norm_gap`` by construction and is
not run).  Prints one JSON line
per run (the compared numbers) and, last, for each number the program's
largest reading and the smallest of the control and of each fault.  The
benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH))

from bench.run import chips_or_refusal, configure_jax  # noqa: E402

BREAKS = [("control_bf16", None), ("half_batch", None),
          ("program", "wrong_row"), ("program", "altered_value")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--break-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--first-seed", type=int, default=2**31 + 1000)
    args = ap.parse_args(argv)
    jax = configure_jax()
    from bench import harness

    refusal = chips_or_refusal(jax, harness.load_cell(args.workload))
    if refusal:
        print(refusal, file=sys.stderr)
        return 2
    runs = [("program", None, args.first_seed + i) for i in range(args.seeds)]
    runs += [(v, f, args.first_seed + 100 + i) for v, f in BREAKS
             for i in range(args.break_seeds)]
    readings = {}
    for variant, fault, seed in runs:
        r = harness.run(args.workload, seed, args.seconds, False, variant=variant, fault=fault)
        kind = fault or variant
        line = {"kind": kind, "seed": seed, "correct": r["correct"],
                "checks": {k: c["value"] for k, c in r["checks"].items()},
                **{k: m["value"] for k, m in r["metrics"].items()}}
        print(json.dumps(line), flush=True)
        readings.setdefault(kind, []).append(line["checks"])
    summary = {}
    for name in readings["program"][0]:
        summary[name] = {"program_max": max(c[name] for c in readings["program"])}
        for kind, cs in readings.items():
            if kind != "program":
                summary[name][f"{kind}_min"] = min(c[name] for c in cs)
    print(json.dumps({"summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
