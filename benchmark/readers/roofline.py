"""A kernel's share of its roofline, in percent: the least time the chip
could take for the bytes the requests need (``bytes`` over the peak
``peak``), over the time the trace says the kernel took (``seconds``).
Bytes-bound by construction (see bytes_model.py).  None where the trace
has no such time: never 0."""

from benchmark.readers import lookup


def read(facts: dict, params: dict):
    n_bytes = lookup(facts, params["bytes"])
    seconds = lookup(facts, params["seconds"])
    peak = lookup(facts, params["peak"])
    if not n_bytes or not seconds or not peak:
        return None
    return 100.0 * (n_bytes / peak) / seconds
