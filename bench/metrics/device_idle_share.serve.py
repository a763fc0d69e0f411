"""Device idle share in a serving cell: 1 - busy / window, from the union
of device-op intervals in the profiler trace of the window."""
from kbench.readers import idle_share


def read(ctx):
    return idle_share(ctx)
