"""Hot-op kernels: pallas TPU kernels with pure-JAX blockwise fallbacks."""

from autodist_tpu.ops.blockwise_attention import blockwise_attention
from autodist_tpu.ops.flash_attention import flash_attention
from autodist_tpu.ops.fused_xent import fused_softmax_xent, matmul_logsumexp


def mosaic_compiles() -> bool:
    """True when pallas kernels compile natively on this backend (TPU). The
    single backend gate for callers choosing kernel-backed configs — on the
    CPU backend pallas runs in interpret mode, orders of magnitude slower."""
    from autodist_tpu.ops.flash_attention import _use_interpret
    return not _use_interpret()


__all__ = ["blockwise_attention", "flash_attention", "fused_softmax_xent",
           "matmul_logsumexp", "mosaic_compiles"]
