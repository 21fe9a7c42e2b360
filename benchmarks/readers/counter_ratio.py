"""A ratio of counters over the window.

``numerator`` and ``denominator`` are lists of counter names; a name
is added, ``-name`` subtracted, ``*name`` multiplies the sum so far.
Nothing to read (a missing counter, a zero denominator) gives None.
"""


def _combine(counters, terms):
    total = 0.0
    for term in terms:
        op, key = (term[0], term[1:]) if term[0] in "-*" else ("+", term)
        value = counters.get(key)
        if value is None:
            return None
        if op == "+":
            total += value
        elif op == "-":
            total -= value
        else:
            total *= value
    return total


def read(obs, numerator, denominator, scale=1.0):
    counters = obs.get("counters", {})
    num, den = _combine(counters, numerator), _combine(counters, denominator)
    if num is None or not den:
        return None
    return scale * num / den
