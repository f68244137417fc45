"""One name per Pallas kernel, carried where a device trace can read it.

A Mosaic custom call reaches the profiler's ``XLA Ops`` line under the name
XLA gives its HLO instruction, and XLA takes that from the innermost scope
of the call's ``op_name`` (``jit(step)/jvp(attn)/pallas_call`` ran as
``jvp_attn_``): whatever scope the call happened to sit in, renamed by
autodiff's ``jvp``/``transpose`` wrappers and by a ``shard_map``. So every
``pallas_call`` of this package goes through :func:`named_pallas_call`,
which gives the kernel its ``name=`` (the Mosaic module's name) and calls it
directly inside a ``jax.named_scope`` of the same name, innermost. Names are
trace-time metadata: the compiled arithmetic is unchanged.
"""

import jax
from jax.experimental import pallas as pl

from autodist_tpu import telemetry

# Every kernel this package lowers, by the name its device events carry
# (the benchmark's kernel readers and tests/test_device_names.py lean on these).
KERNEL_NAMES = ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq", "flash_carry",
                "xent_fwd", "xent_bwd_dh", "xent_bwd_dw",
                "moe_gmm_fwd", "moe_gmm_bwd_dx", "moe_gmm_bwd_dw",
                "short_conv_fwd", "short_conv_bwd", "moe_rows_gather",
                "moe_rows_combine", "ssd_fwd", "ssd_bwd", "conv_silu_fwd",
                "conv_silu_bwd", "selective_scan_fwd", "selective_scan_bwd",
                "flash_sink_fwd", "flash_sink_bwd_dkv", "flash_sink_bwd_dq",
                "gated_norm_fwd", "gated_norm_bwd", "eva_fwd", "eva_bwd",
                "kda_fwd", "kda_bwd")


def named_pallas_call(name: str, kernel, **kwargs):
    """``pl.pallas_call(kernel, name=name, **kwargs)`` whose call sits
    directly inside ``jax.named_scope(name)``."""
    if name not in KERNEL_NAMES:
        raise ValueError(f"kernel name {name!r} is not in KERNEL_NAMES")
    call = pl.pallas_call(kernel, name=name, **kwargs)
    # Runs when the call site is traced (every site is traced and lowered
    # again, which set-up pays for), never in a step: the set-up ledger's
    # count of traced kernel call sites, all and by kernel.
    telemetry.counter("jit.kernel_call_sites").inc()
    telemetry.counter(f"jit.kernel_call_sites.{name}").inc()

    def run(*args):
        with jax.named_scope(name):
            return call(*args)

    return run
