"""Device time of one reachability closure build: the mean duration of the
closure builders of ``core/queries.py`` (``_build_closure_pallas``,
``_build_closure_jnp``) in the trace, per build."""
from kbench.readers import mean_ms, module_events

PATTERNS = [r"_build_closure"]


def read(ctx):
    return mean_ms(module_events(ctx, PATTERNS))
