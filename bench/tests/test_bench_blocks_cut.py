"""The reader of ``loader.blocks_cut_share`` on hand-made counters with known
answers, and on counters of a program that keeps no ``blocks_cut``."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src")) if p not in sys.path]

from bench import harness  # noqa: E402


class Reading:
    def __init__(self, **counters):
        self.counters = counters


def _read(r):
    return harness.load_module("metrics", "loader.blocks_cut_share").read(r)


def test_reader_gives_known_answers():
    assert _read(Reading(blocks_cut=10, cache_misses=186, rows=16384)) == pytest.approx(10 / 186)
    assert _read(Reading(blocks_cut=186, cache_misses=186)) == 1.0
    assert _read(Reading(blocks_cut=0, cache_misses=40)) == 0.0


def test_reader_finds_nothing_without_the_counter_or_a_miss():
    assert _read(Reading(cache_misses=186, runs=14, rows=16384)) is None
    assert _read(Reading(blocks_cut=0, cache_misses=0)) is None
