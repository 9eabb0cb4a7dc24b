"""A kernel's share of its roofline, in percent, where the bytes the
REQUESTS need are a count times a fixed number of bytes: the least time
the chip could take for them (``count`` x ``bytes_per_count`` over the
peak ``peak``), over the time the trace gives the programs whose name
starts with ``prefix`` (``modules``: a name -> seconds map).  Bytes-bound
by construction, like ``roofline``.  ``bytes_per_count`` is stated by
the metric's file and has to hold for the cell's configuration (every
counted unit needs exactly that many bytes).  None where a part is
missing, the count is 0 or no program of that name ran: never 0."""

from benchmark.readers import lookup


def read(facts: dict, params: dict):
    count = lookup(facts, params["count"])
    names = lookup(facts, params["modules"])
    peak = lookup(facts, params["peak"])
    if not count or not isinstance(names, dict) or not peak:
        return None
    seconds = sum(v for name, v in names.items()
                  if name.startswith(params["prefix"]))
    if not seconds:
        return None
    return 100.0 * (count * params["bytes_per_count"] / peak) / seconds
