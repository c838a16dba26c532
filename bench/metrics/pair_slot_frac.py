"""Share of the padded pair table that the cycle program's pair passes ran
over: each cycle's ``pair_slots`` (the whole table on a full trip, the
compacted bucket on a sparse one, nothing on a skipped one) over its
``substeps`` times ``pair_table_slots`` (every trip over the whole table).
Cycles whose stats do not count pair slots are left out; a program that
counts none gives nothing."""


def read(run):
    stats = [s for s in run["cycle_stats"] if "pair_slots" in s]
    total = sum(s["substeps"] * s["pair_table_slots"] for s in stats)
    if not total:
        return None
    return sum(s["pair_slots"] for s in stats) / total
