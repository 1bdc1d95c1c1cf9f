"""A quotient of two ``/metrics`` deltas over a span of the run: a
histogram's ``_sum`` over its ``_count`` (a mean), or one counter's growth
per unit of another's. ``num`` and ``den`` are ``{"name", "labels"}``; a
label value is a regular expression matched in full. ``span`` is two of
``open``, ``close``, ``end`` (window opens, window closes, model published).
Returns nothing where the denominator did not move."""

from benchmark.harness.coordinator import sample_sum


def read(ctx: dict, num: dict, den: dict, span: list, scale: float = 1.0):
    lo, hi = (ctx["metrics"].get(k) for k in span)
    if lo is None or hi is None:
        return None
    d_num = sample_sum(hi, num["name"], num.get("labels")) - sample_sum(lo, num["name"], num.get("labels"))
    d_den = sample_sum(hi, den["name"], den.get("labels")) - sample_sum(lo, den["name"], den.get("labels"))
    if d_den <= 0:
        return None
    return scale * d_num / d_den
