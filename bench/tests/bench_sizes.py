"""A tiny atlas and traffic, so a cell runs end to end on the CPU in seconds."""
import copy

TINY = {
    "config": {
        "n_cells": 4096, "n_genes": 512, "total_counts": 256,
        "generator": {"draws": {"base": 160, "line": 60, "drug": 24, "plate": 12},
                      "base_sigma": 1.75, "signature_sigma": 1.0,
                      "signature_genes": {"line": 64, "moa": 16, "drug": 8, "plate": 32},
                      "plate_line_alpha": 0.3},
    },
    # two warm-up fetches: the tiny atlas fits the block cache, and a longer
    # warm-up would leave the window no reads for the counters to count
    "traffic": {"batch_size": 16, "fetch_factor": 8, "strategy_params": {"block_size": 4},
                "warmup_fetches": 2},
}


def tiny(**traffic) -> dict:
    out = copy.deepcopy(TINY)
    out["traffic"].update(traffic)
    return out
