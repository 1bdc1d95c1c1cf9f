"""One ``/metrics`` value as it stood at an instant of the run (``at`` is
``open``, ``close`` or ``end``). Returns nothing where the series is absent."""

from benchmark.harness.coordinator import sample_sum


def read(ctx: dict, name: str, at: str, labels: dict | None = None, scale: float = 1.0):
    samples = ctx["metrics"].get(at)
    if samples is None or not any(s[0] == name for s in samples):
        return None
    return scale * sample_sum(samples, name, labels)
