"""A number from the coordinator's ``/healthz`` at an instant of the run:
``path`` walks the JSON; a list is reduced by its maximum. ``over_peak``
names a key of the device's row in ``peaks.json`` to divide by."""


def read(ctx: dict, path: list, at: str, scale: float = 1.0, over_peak: str | None = None):
    node = ctx["health"].get(at)
    for key in path:
        if not isinstance(node, dict) or key not in node:
            return None
        node = node[key]
    if isinstance(node, list):
        values = [v for v in node if isinstance(v, (int, float))]
        if not values:
            return None
        node = max(values)
    if not isinstance(node, (int, float)) or isinstance(node, bool):
        return None
    if over_peak is not None:
        if ctx.get("peak") is None:
            return None
        return scale * node / ctx["peak"][over_peak]
    return scale * node
