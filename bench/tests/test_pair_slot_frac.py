"""``pair_slot_frac`` from the cycle stats as the harness hands them over."""

import pytest

from harness.cell import load_module

read = load_module("metrics", "pair_slot_frac").read


def test_nothing_from_a_program_that_counts_no_pair_slots():
    stats = [{"substeps": 16, "force_substeps": 10, "updates": 223008}] * 3
    assert read({"cycle_stats": stats}) is None
    assert read({"cycle_stats": []}) is None


def test_share_of_the_whole_table_over_every_trip():
    # one full trip and nine compacted ones of 1/32 of the table, six
    # skipped: (1 + 9/32) of 16 trips' tables
    full, bucket = 262144, 8192
    stats = [{"substeps": 16, "pair_table_slots": full,
              "pair_slots": full + 9 * bucket, "compact_trips": 9,
              "skipped_trips": 6}] * 2
    assert read({"cycle_stats": stats}) == pytest.approx((1 + 9 / 32) / 16)
    # a replayed cycle (host ladder, no counter) is left out
    replayed = {"substeps": 16, "force_substeps": 10}
    assert read({"cycle_stats": stats + [replayed]}) \
        == pytest.approx((1 + 9 / 32) / 16)
    # four trips, one live on the whole table
    four = [{"substeps": 4, "pair_table_slots": 4096, "pair_slots": 4096}]
    assert read({"cycle_stats": four}) == 0.25
