"""``scale * num / den``, or with ``complement`` ``scale * (1 - num/den)``.
None where either is missing or the denominator is 0."""

from benchmark.readers import lookup


def read(facts: dict, params: dict):
    num = lookup(facts, params["num"])
    den = lookup(facts, params["den"])
    if num is None or not den:
        return None
    share = num / den
    if params.get("complement"):
        share = 1.0 - share
    return params.get("scale", 1.0) * share
