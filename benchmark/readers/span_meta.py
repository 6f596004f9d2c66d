"""What the spans say they did, over the occurrences of ``span`` that began
in the traced stretch: the mean of the meta key ``key``; or, with ``num`` and
``den``, the two keys each summed and divided, as a percentage, and taken
from 100 where ``complement`` is set (1 - prompt tokens over bucket sizes
is the padding).  None where no such span carries the keys: a program that
does not write them."""


def read(ctx, params):
    metas = [m for n, s, _, _, m in ctx["spans"] or []
             if n == params["span"] and ctx["t0"] <= s < ctx["t1"]]
    if "key" in params:
        vals = [m[params["key"]] for m in metas if params["key"] in m]
        return sum(vals) / len(vals) if vals else None
    num, den = params["num"], params["den"]
    both = [m for m in metas if num in m and den in m]
    total = sum(m[den] for m in both)
    if not total:
        return None
    share = 100.0 * sum(m[num] for m in both) / total
    return 100.0 - share if params.get("complement") else share
