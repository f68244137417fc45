"""The Pallas kernels one by one: what each group of them must do for one
optimizer step (from ``benchmark/flops.py`` on the cell's own configuration
and traffic) and how long the traced steps spent in it (self seconds of the
device operations named after it, all chips).

The program names every kernel (``autodist_tpu/ops/named_call.py``): its
Mosaic custom call reaches the trace's ``XLA Ops`` line as
``%flash_fwd.3 = ... custom-call(...)``, so ``trace_reduce.group`` files it
under ``pallas:flash_fwd`` whatever scope, ``jvp``/``transpose`` wrapper or
``shard_map`` the call sat in. A program without that module (one older than
the names) gives a reader nothing to read: None. A program *with* it whose
trace holds Mosaic time under none of its names is a fault, not a zero: the
persistent compile cache leaves metadata out of its key, so a ``.jax_cache``
that an older build filled hands back programs with the old names, and a
refactor can lose a scope. That raises, and the run fails.

The split of ``flash_attention_cost``: of the seven score-sized products the
forward runs two (q.k^T, p.v) and the backward five (the scores again, dP,
dV, dQ, dK); of the twelve tensors moved the forward reads q, k, v and writes
o (four), the backward reads q, k, v, o, dO and writes dq, dk, dv (eight).
``parts`` sum to the job's ``kernel_cost_per_step``, which a test holds.
"""

import math

from benchmark import flops, harness

FLASH_FWD = ("flash_fwd",)
FLASH_BWD = ("flash_bwd_dkv", "flash_bwd_dq")
XENT = ("xent_fwd", "xent_bwd_dh", "xent_bwd_dw")
PREFIX = "pallas:"


def parts(cell) -> dict:
    """``{"flash_fwd", "flash_bwd", "xent"}`` -> ``flops.KernelCost`` of one
    optimizer step on all chips, or None where the configuration runs no
    Pallas kernel. Mirrors ``families/transformer_lm.build``."""
    config, traffic = cell.config, cell.traffic
    if not config.get("expects_pallas"):
        return None
    calls = traffic["accumulation"]
    micro = traffic["micro_batch"] * math.prod(traffic["mesh"].values())
    flash = flops.flash_attention_cost(
        batch=micro, seq_len=traffic["seq_len"], n_heads=config["n_head"],
        head_dim=config["n_embd"] // config["n_head"], causal=True) \
        * (config["n_layer"] * calls)
    xent = flops.fused_xent_cost(
        rows=micro * traffic["seq_len"], d_model=config["n_embd"],
        vocab_size=config["vocab_size"]) * calls
    return {"flash_fwd": flops.KernelCost(flash.flops * 2 / 7,
                                          flash.hbm_bytes * 4 / 12),
            "flash_bwd": flops.KernelCost(flash.flops * 5 / 7,
                                          flash.hbm_bytes * 8 / 12),
            "xent": xent}


def program_kernel_names():
    """The names the program gives its kernels, or None where it has none."""
    try:
        from autodist_tpu.ops import named_call
    except ImportError:
        return None
    return tuple(named_call.KERNEL_NAMES)


def group_seconds(trace, names) -> float:
    """Self seconds under ``pallas:<name>`` for ``names``, all chips."""
    return sum(d.by_group.get(PREFIX + n, 0.0)
               for d in trace.devices.values() for n in names)


def roofline_pct(record, part: str, names):
    """Least seconds of ``part`` for the traced steps over the seconds the
    trace holds under ``names``, in percent; None where there is nothing to
    read (no device trace, no kernel in the configuration, a program that
    names no kernel)."""
    trace, steps = record.get("trace"), record.get("trace_steps")
    peaks = record.get("peaks")
    if trace is None or not steps or peaks is None:
        return None
    costs = parts(record["cell"])
    known = program_kernel_names()
    if costs is None or known is None:
        return None
    measured = group_seconds(trace, names)
    if measured <= 0:
        found = sorted({g for d in trace.devices.values() for g in d.by_group
                        if g.startswith(PREFIX)})
        raise harness.BenchmarkError(
            f"{record['cell'].name}: the program names its kernels {known}, "
            f"and the trace holds no time under {names}; Mosaic groups in the "
            f"trace: {found}. A compile cache filled by an older build hands "
            f"back programs with the old names: empty .jax_cache")
    return 100.0 * costs[part].least_seconds(peaks) * steps / measured
