"""Device time of one publish (merge of the delta into the front and the
delta's reset): the mean duration of the jitted ``_publish`` program of
``serving/snapshot.py`` in the trace, per epoch."""
from kbench.readers import mean_ms, module_events

PATTERNS = [r"(^|_)_publish(\b|_|\()", r"jit__publish"]


def read(ctx):
    return mean_ms(module_events(ctx, PATTERNS))
