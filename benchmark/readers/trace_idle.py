"""The device's idle share of the traced part of the window: 1 minus the
union of its operations' intervals over the window's length, in percent."""


def read(ctx: dict):
    trace = ctx.get("trace")
    if not trace or trace.get("device_stand_in") or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
