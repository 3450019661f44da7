"""The host's wait for the chunk program per round, in ms: the
``host_sync/device_wait`` span (``jax.block_until_ready`` on the tick's
outputs)."""
from perfbench.harness import scopes


def read(ctx):
    return scopes.span_ms_per_round(ctx, "host_sync/device_wait")
