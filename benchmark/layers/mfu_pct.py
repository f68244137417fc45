"""Device: model FLOP/s utilization. Operations the forward and backward
passes require per token (``benchmark/flops.py``; recomputation not counted)
times tokens per second per chip, over the chip's published bf16 peak."""


def read(record):
    peaks = record.get("peaks")
    if peaks is None:
        return None
    return (100.0 * record["train_flops_per_token"]
            * record["end_to_end"]["tokens_per_s_per_chip"] / peaks.bf16_flops_per_s)
