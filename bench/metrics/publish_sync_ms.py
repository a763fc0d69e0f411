"""Host wait of one publish for the device: the program's
``kmatrix.snapshot.publish_sync`` spans (the ``device_get`` of the pending
edge count, which waits for every dispatch in flight) that started in the
window, mean per publish."""
from kbench.spans import mean_per_key_ms


def read(ctx):
    return mean_per_key_ms(ctx, {"kmatrix.snapshot.publish_sync"})
