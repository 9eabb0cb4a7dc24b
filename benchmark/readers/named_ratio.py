"""``scale * (sum of the entries of a name -> value map whose name starts
with prefix) / den``: a part of the device trace chosen by the program's
own names (``jit_ec_encode...``), over a count.  None where the map is
missing, no name matches, or the denominator is 0: a program that does
not name its programs so (the parent commit) reports nothing."""

from benchmark.readers import lookup


def read(facts: dict, params: dict):
    names = lookup(facts, params["num"])
    den = lookup(facts, params["den"])
    if not isinstance(names, dict) or not den:
        return None
    matched = [v for name, v in names.items()
               if name.startswith(params["prefix"])]
    if not matched:
        return None
    return params.get("scale", 1.0) * sum(matched) / den
