"""Device-to-host copies of the tick's outputs per round, in ms: the
``host_sync/copy_out`` span (one ``np.asarray`` per output, after the
wait)."""
from perfbench.harness import scopes


def read(ctx):
    return scopes.span_ms_per_round(ctx, "host_sync/copy_out")
