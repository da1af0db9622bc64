"""booster_init_self_s: the setup/booster_init span (engine.train: create_booster to the first iteration's start) less the union of the spans inside it (setup/transfer, setup/objective_init, setup/add_valid, compile/*): the part of booster construction that still has no name.  A program whose booster_init has no children (older than PR 37) reads the whole span."""

from benchmark import setup_spans


def read(facts):
    return setup_spans.init_self_seconds(facts)
