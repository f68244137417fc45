"""Device: what the fullest chip holds at a log boundary beside the
``TrainState`` (params, optimizer state, error feedback, step, by that
chip's shards), in GiB (gauge ``train.hbm.unowned_bytes``): the caller's
copy of the parameters held for the whole of ``train()``, device batches, a
snapshot ring, leaks. None where the program read no allocator."""

from benchmark import hbm_account


def read(record):
    return hbm_account.gib("train.hbm.unowned_bytes")
