"""Host work per round outside admission and the chunk program, in ms:
the round's wall time on the harness clock minus the ``admit_drain``,
``chunk_execute`` and ``host_sync`` spans (left: mint planning, the
conservation check, slot recycling, the telemetry fold)."""

PHASES = ("admit_drain", "chunk_execute", "chunk_compile_execute",
          "host_sync")


def read(ctx):
    p = ctx.get("phases") or {}
    if "host_sync" not in p or not ctx["rounds"]:
        return None
    rest = ctx["window_s"] - sum(p.get(k, 0.0) for k in PHASES)
    return rest / ctx["rounds"] * 1e3
