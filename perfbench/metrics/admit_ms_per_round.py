"""Host admission per round: the service's ``admit_drain`` phase span
(queue offer and drain, slot placement, the COO demand write), in ms."""


def read(ctx):
    p = ctx.get("phases") or {}
    if "admit_drain" not in p or not ctx["rounds"]:
        return None
    return p["admit_drain"] / ctx["rounds"] * 1e3
