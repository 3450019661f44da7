"""Device busy time per round, in ms: the union of the device-op intervals
of the profiler trace inside the measured window, over its rounds."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or not ctx["rounds"] or tr["busy_s"] <= 0:
        return None
    return tr["busy_s"] / ctx["rounds"] * 1e3
