"""Device: what the allocator's limit leaves a chip above what the account
predicts it holds while a step runs, in GiB (gauge ``train.hbm.headroom_bytes``
= limit - predicted, predicted = resident + (argument - state) + temp + output
- alias; the prediction on standard error): the room a larger micro-batch or a
longer ``KEPT`` list can spend. None where the program read no allocator or
booked no step account."""

from benchmark import hbm_account


def read(record):
    value = hbm_account.gib("train.hbm.headroom_bytes")
    if value is not None:
        hbm_account.say("the identity", "train.hbm.predicted_bytes")
    return value
