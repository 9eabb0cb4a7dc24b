"""One fact as it stands (a counter's change over the window)."""

from benchmark.readers import lookup


def read(facts: dict, params: dict):
    return lookup(facts, params["fact"])
