"""in_bag_row_share: the rows a traced tree was grown on over the rows of the table, in per cent: the root's internal_count of the model's own dump (under row sampling the model's counts are in-bag counts, as upstream's), averaged over the traced trees."""

from benchmark import readers


def read(facts):
    if readers._traced_iterations(facts) <= 0:
        return None
    roots = [t.get("internal_count") for t in readers._traced_trees(facts)]
    roots = [float(r) for r in roots if r is not None]
    rows = float(facts["rows"])
    if not roots or rows <= 0:
        return None
    return 100.0 * sum(roots) / len(roots) / rows
