"""Host side of one sketch ingest step: the program's
``kmatrix.worker.stage`` (staging fill, padding, ``EdgeBatch.from_numpy``)
and ``kmatrix.worker.dispatch`` (the asynchronous jitted ingest call and
its completion fence) spans that started in the window, summed per
dispatch and averaged over dispatches."""
from kbench.spans import mean_per_key_ms


def read(ctx):
    return mean_per_key_ms(ctx, {"kmatrix.worker.stage",
                                 "kmatrix.worker.dispatch"})
