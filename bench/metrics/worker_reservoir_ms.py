"""Host time of the ingest worker's online reservoir sample
(``Reservoir.offer_batch`` over the dispatch's items, inside the worker's
state lock): the program's ``kmatrix.worker.reservoir`` spans that started
in the window, mean per dispatch."""
from kbench.spans import mean_per_key_ms


def read(ctx):
    return mean_per_key_ms(ctx, {"kmatrix.worker.reservoir"})
