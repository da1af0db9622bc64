"""split_scan_ms_per_iter: non-kernel operations of the grow programs under the scope split_scan (ops/grower.py cand_for_leaf), per traced iteration."""

from benchmark import scope_join


def read(facts):
    return scope_join.grow_ms_per_iter(facts, "split_scan")
