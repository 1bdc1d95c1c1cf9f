"""A number the load generator measured about itself or about its uploads
(``benchmark/harness/replay.py``): lateness, offered rate, upload tails."""


def read(ctx: dict, key: str, scale: float = 1.0):
    value = ctx["driver"].get(key)
    return None if value is None else scale * value
