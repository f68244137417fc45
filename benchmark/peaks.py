"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports. A kind that is not here is an error, never a
default: a utilization against a guessed peak is not a measurement."""

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops_per_s: float
    hbm_bytes_per_s: float
    hbm_bytes: int
    source: str


PEAKS = {
    # One TPU v5e chip. JAX names it "TPU v5 lite".
    "TPU v5 lite": Peaks(
        bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16 * 10**9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM2e at 819 GB/s per chip"),
}


class UnknownDevice(RuntimeError):
    """The device is in no peak table."""


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"device_kind {device_kind!r} is not in benchmark/peaks.py "
            f"(known: {sorted(PEAKS)}): add its published peaks with their "
            f"source before measuring on it") from None
