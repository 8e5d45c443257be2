"""Consumer step, across chips: the rate of the heads' gradient all-reduce,
in GB/s per chip.

A ring all-reduce over n chips makes each chip send, and receive, 2(n-1)/n
of the reduced bytes: here the float32 gradient of the Fig. 5 step's four
heads, n_genes x the classes of every task plus a bias for each class.
Those bytes over ``consumer.allreduce_ms`` (the collectives' device time
per step).  n is the number of device planes in the trace; ``n_genes`` is
the configuration's, of the cells ``BENCHMARK.json`` lists this metric for.
None on one chip, or where the step ran no collective."""
import os

from bench import harness

NAME = "consumer.allreduce_gbps"


def allreduce_bytes(n_genes: int, tasks: dict, n: int) -> float:
    """Bytes one chip moves in a ring all-reduce of the heads' gradient."""
    classes = sum(tasks.values())
    return 2 * (n - 1) / n * 4 * (n_genes * classes + classes)


def n_genes() -> int:
    """The genes of the cells this metric is listed for; they must agree."""
    spec = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    (entry,) = [m for m in spec["per_layer"] if m["name"] == NAME]
    (genes,) = {int(harness.load_cell(c).config["n_genes"]) for c in entry["workloads"]}
    return genes


def read(r):
    ms = harness.load_module("metrics", "consumer.allreduce_ms").read(r)
    n = len(r.trace.ops)
    if not ms or n < 2:
        return None
    tasks = harness.load_module("consumers", "fig5").TASKS
    return allreduce_bytes(n_genes(), tasks, n) / (ms / 1e3) / 1e9
