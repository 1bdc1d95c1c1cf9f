"""A number from a span of the process the reader runs in: the harness's
own, where the SDK's sum participant runs (``benchmark/run.py`` ticks it),
which no ``/metrics`` scrape reaches. The tracer's ring
(``xaynet_tpu.telemetry.tracing``) is read for the **last** finished span
named ``span``: the measured round's Sum2 is the last thing the participant
did, so that is the measured round's and not the warm-up's. ``field`` is
``dur`` (the span's seconds), an attribute, or a list of attributes to add
up; with ``per`` the value is divided by the span's seconds times that
attribute (CPU seconds over the wall of ``threads`` threads: the share of
it they had a core). Returns nothing where the ring has no such span, where
the span lacks an attribute asked for, and on a program whose spans record
no usage (before PR 52: the participant's leg is read whole or not at all)."""


def read(ctx: dict, span: str, field, per: str | None = None, scale: float = 1.0):
    from xaynet_tpu.telemetry import tracing

    if not hasattr(tracing, "usage_span_names"):
        return None
    return reduce(tracing.get_tracer().ring_spans(), span, field, per, scale)


def reduce(spans, span: str, field, per: str | None = None, scale: float = 1.0):
    """``read`` over a given list of spans, oldest first."""
    last = next((s for s in reversed(spans) if s.name == span), None)
    if last is None:
        return None
    values = [last.duration if key == "dur" else last.attrs.get(key)
              for key in ([field] if isinstance(field, str) else field)]
    if any(not isinstance(v, (int, float)) or isinstance(v, bool) for v in values):
        return None
    value = float(sum(values))
    if per is not None:
        share = last.attrs.get(per)
        if not isinstance(share, (int, float)) or share <= 0 or last.duration <= 0:
            return None
        value /= last.duration * share
    return scale * value
