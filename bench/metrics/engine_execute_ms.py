"""Host time of one ``QueryEngine.execute`` call: the benchmark's own span
around each call that started in the window, mean per batch."""


def read(ctx):
    w = ctx.window
    spans = [t_b - t_a for _, _, t_a, t_b, *_ in w.query_batches
             if w.t_open <= t_a < w.t_close]
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
