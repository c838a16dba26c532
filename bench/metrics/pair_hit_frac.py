"""Share of the particle–neighbour entries that the closing trip's density
pass evaluated which did SPH work: each cycle's ``pair_hits`` (entries with
W(r, h_i) > 0 summed into a real particle's density, over the rows of rank
0, counted on the cycle's last trip, which runs the whole padded table)
over ``pair_table_slots`` × ``capacity``² × 2. A pair slot is a
(capacity × capacity) block evaluated in both directions; on a self pair
the second direction is evaluated and discarded, and counts as evaluated.
What is left is the cost of capacity padding, table padding and a grid
whose cells are sized by the largest h. Cycles whose stats lack
``pair_hits`` are left out; a program that counts none gives nothing."""


def read(run):
    stats = [s for s in run["cycle_stats"] if "pair_hits" in s]
    total = sum(2 * s["pair_table_slots"] * s["capacity"] ** 2
                for s in stats)
    if not total:
        return None
    return sum(s["pair_hits"] for s in stats) / total
