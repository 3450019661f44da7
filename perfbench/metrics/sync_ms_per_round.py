"""The chunk program as the host waits for it, in ms per round: the
``chunk_execute`` span (dispatch) plus ``host_sync`` (the wait for the chip
and the copy of the tick's outputs)."""

PHASES = ("chunk_execute", "chunk_compile_execute", "host_sync")


def read(ctx):
    p = ctx.get("phases") or {}
    if "host_sync" not in p or not ctx["rounds"]:
        return None
    return sum(p.get(k, 0.0) for k in PHASES) / ctx["rounds"] * 1e3
