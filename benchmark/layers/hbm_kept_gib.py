"""Device: what the step's checkpointed layers keep for their backward, as a
chip's share, in GiB (gauge ``step.hbm.kept_bytes``: the named values the
``KEPT`` list's policy kept in the step's own trace, ``remat.kept_bytes``,
over the data shards the batch is split in): a ``KEPT`` list's price in the
unit of ``hbm_headroom_gib``. None where no layer is checkpointed, or the
program read no allocator."""

from benchmark import hbm_account


def read(record):
    return hbm_account.gib("step.hbm.kept_bytes")
