"""The benchmark's entry point: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints set-up parts, the window and each number compared beside its limit
on standard error, and as the last line of standard output one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` ``breakdown``, and ``checks`` last).  Exits nonzero, before any
set-up, where JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
REQUIRED_PLATFORM = "tpu"


def configure_jax():
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (the path is part of each entry's key), caching every program."""
    import jax

    jax.config.update("jax_compilation_cache_dir", os.path.join(BENCH, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def chips_or_refusal(jax, cell) -> str:
    """An empty string where JAX holds the cell's chips, else why not."""
    devices = jax.devices()
    if devices[0].platform != REQUIRED_PLATFORM or len(devices) < cell.chips:
        return (f"bench: cell {cell.name} needs {cell.chips} {REQUIRED_PLATFORM} chip(s); "
                f"JAX found {len(devices)} {devices[0].platform} device(s)")
    return ""


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    jax = configure_jax()
    sys.path.insert(0, os.path.dirname(BENCH))
    from bench import harness

    cell = harness.load_cell(args.workload)
    refusal = chips_or_refusal(jax, cell)
    if refusal:
        print(refusal, file=sys.stderr)
        return 2
    result = harness.run(args.workload, args.seed, args.seconds, bool(args.trace),
                         t_start=T_START)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
