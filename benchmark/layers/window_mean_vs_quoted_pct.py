"""Loop: the rate over the whole window (steps between the first and the
last fenced boundary over the time between them) as a share of the rate
``tokens_per_s_per_chip`` quotes, which is that of the lower-quartile log
period (``jobs/train.py`` ``undisturbed_step_seconds``). 100 when every
period takes the same time; whatever slows some periods and not others,
which the quoted rate does not see, pulls it under 100 by the time lost."""


def read(record):
    mean, quoted = record.get("mean_rate"), record["end_to_end"].get("tokens_per_s_per_chip")
    if not mean or not quoted:
        return None
    return 100.0 * mean / quoted
