"""Host time of the ingest worker's exact duplicate-edge pre-aggregation
(the concatenates and ``preaggregate_edges``): the program's
``kmatrix.worker.dedup`` spans that started in the window, mean per
dispatch."""
from kbench.spans import mean_per_key_ms


def read(ctx):
    return mean_per_key_ms(ctx, {"kmatrix.worker.dedup"})
