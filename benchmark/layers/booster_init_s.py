"""booster_init_s: duration of the setup/booster_init span (engine.train: create_booster to the first iteration's start), from the span ring."""

from benchmark import scope_join


def read(facts):
    return scope_join.booster_init_s(facts)
