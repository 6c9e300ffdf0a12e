"""batch_occupancy.open: queries per drain of the shard batcher over the
window (search_batch queries_dispatched / batches_dispatched)."""

from benchmark.readers import Window, batch_delta


def read(w: Window):
    batches = batch_delta(w, "batches_dispatched")
    if batches <= 0:
        return None
    return batch_delta(w, "queries_dispatched") / batches
