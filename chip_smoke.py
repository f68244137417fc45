#!/usr/bin/env python3
"""Chip smoke: the quickest proof that the system still starts on the TPU.

Drives the flagship trainer once through the entry points a user calls —
``AutoDist(...).function`` and ``training.train`` — at the flagship's full
width (depth and step count are what keep it short; weights and tokens are
random, from ``SEED``), runs the two Pallas kernels against their plain
references on the device, and shows the persistent compile cache being hit.
One process, which is the only one to touch JAX; it starts no child.

    python chip_smoke.py            # one chip: device, native, train, kernels, cache
    python chip_smoke.py --chips 4  # four chips: the sharded step against one device

Each phase prints one JSON line of facts. The last line of standard output is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and the exit code is 0. The first failed phase ends the run with ``"ok":
false`` on the last line and exit code 1; no phase is skipped, and there is no
CPU fallback: without a TPU the device phase fails. ``run()`` takes a size and
``require_tpu`` as arguments so ci.sh and the tests can rehearse the same
phases on the CPU mesh at a tiny width.
"""

import argparse
import dataclasses
import json
import sys
import time
import traceback

SEED = 0


@dataclasses.dataclass(frozen=True)
class Size:
    """Everything a run's cost depends on. ``FLAGSHIP`` is what the script
    runs; ``TINY`` is the CPU rehearsal of the same phases."""

    vocab_size: int
    d_model: int
    n_heads: int
    n_layers: int
    d_ff: int
    max_len: int
    seq_len: int
    micro_batch: int        # sequences per device per micro-batch
    accum: int              # micro-batches per optimizer step
    steps: int              # measured steps after the two warm-up steps
    loop_steps: int         # steps through training.train()
    flash_shape: tuple      # (B, L, H, D) of the flash-attention check
    xent_rows: int          # rows of the fused-xent check (logits must fit)
    sharded_batch: int      # --chips 4: global sequences, one chip holds them


FLAGSHIP = Size(vocab_size=32_000, d_model=512, n_heads=8, n_layers=6,
                d_ff=2048, max_len=512, seq_len=256, micro_batch=384, accum=8,
                steps=5, loop_steps=3, flash_shape=(4, 2048, 8, 64),
                xent_rows=8192, sharded_batch=384)
TINY = Size(vocab_size=512, d_model=64, n_heads=2, n_layers=1, d_ff=128,
            max_len=32, seq_len=16, micro_batch=2, accum=2, steps=2,
            loop_steps=2, flash_shape=(1, 128, 2, 64), xent_rows=64,
            sharded_batch=8)

# Stated tolerances. Kernels compute in bf16 with f32 accumulation and are
# compared with float32 references, as max |got - ref| over max |ref|.
KERNEL_TOL = 3e-2
# Sharded against single-device loss of the same batch and seed, relative.
SHARDED_LOSS_RTOL = 5e-3


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def _emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def _fence(x):
    """Completion fence: block on the device, then read the value back."""
    import jax
    return float(jax.block_until_ready(x))


class _CacheEvents:
    """Counts JAX's persistent-compile-cache hits and misses (a miss is a
    program compiled and then written) while used as a context manager."""

    def __init__(self):
        self.hits = self.misses = 0

    def __call__(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def __enter__(self):
        from jax import monitoring
        monitoring.register_event_listener(self)
        return self

    def __exit__(self, *exc):
        from jax import monitoring
        monitoring.unregister_event_listener(self)


# ------------------------------------------------------------------- phases

def describe_device() -> dict:
    """The device as JAX reports it: what the result line carries."""
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def phase_device(device: dict, require_tpu: bool, chips: int) -> None:
    import jax

    from autodist_tpu.telemetry import profiling
    from autodist_tpu.utils import compile_cache

    if require_tpu and device["platform"] != "tpu":
        raise SmokeFailure(
            f"no TPU: jax.devices()[0].platform is {device['platform']!r}. "
            f"chip_smoke.py measures nothing on a CPU; run it on the chip")
    if require_tpu and device["count"] != chips:
        raise SmokeFailure(f"{device['count']} device(s) visible, this run "
                           f"is for {chips}")
    peaks = profiling.peak_spec(jax.devices()[0])
    if require_tpu and (peaks.flops_per_s is None
                        or peaks.membw_bytes_per_s is None):
        raise SmokeFailure(
            f"device_kind {device['kind']!r} is in no peak table "
            f"(telemetry/profiling.py PEAK_BF16_FLOPS / PEAK_HBM_BYTES): "
            f"source={peaks.source!r}")
    _emit({"phase": "device", **device, "peaks": peaks.to_dict(),
           "compile_cache_dir": compile_cache.configure(),
           "jax": jax.__version__})


def phase_native() -> None:
    """Report — not require — the native builds: a chip machine without a
    compiler runs the Python loader and transport, and this line shows it."""
    from autodist_tpu.data import loader
    from autodist_tpu.parallel import ps_transport
    _emit({"phase": "native", "loader": loader._build_native() is not None,
           "transport": ps_transport._native_transport() is not None})


def flagship_config(size: Size):
    import jax.numpy as jnp

    from autodist_tpu.models import transformer_lm
    return transformer_lm.TransformerLMConfig(
        vocab_size=size.vocab_size, d_model=size.d_model, n_heads=size.n_heads,
        n_layers=size.n_layers, d_ff=size.d_ff, max_len=size.max_len,
        dtype=jnp.bfloat16, tied_output=False,
        # Pallas fused head+loss: logits never materialize, which is what
        # lets 384 sequences per micro-batch fit the chip.
        fused_head=True)


def flagship_model(size: Size, batch_size: int):
    """(cfg, params, loss_fn, batch): random weights and tokens from SEED."""
    import jax

    from autodist_tpu.models import transformer_lm

    cfg = flagship_config(size)
    model, params = transformer_lm.init_params(cfg, rng=jax.random.PRNGKey(SEED))
    batch = transformer_lm.synthetic_batch(cfg, batch_size=batch_size,
                                           seq_len=size.seq_len, seed=SEED)
    return cfg, params, transformer_lm.make_loss_fn(model), batch


def build_flagship(size: Size, batch_size: int, accum: int, strategy=None,
                   resource_spec=None):
    """The flagship trainer as a user builds it: model, synthetic batch and
    the ``AutoDist.function`` step (AllReduce unless told otherwise). Shared
    with ``bench.py``'s flagship mode. Returns (cfg, params, batch, step)."""
    import optax

    from autodist_tpu import AutoDist
    from autodist_tpu.strategy import AllReduce

    cfg, params, loss_fn, batch = flagship_model(size, batch_size)
    ad = AutoDist(resource_spec, strategy_builder=strategy or AllReduce())
    step = ad.function(loss_fn, params, optax.adam(1e-3), example_batch=batch,
                       accumulation_steps=accum)
    return cfg, params, batch, step


def _first_step(step, batch):
    """(loss, seconds) of one fenced call: compile + first dispatch when the
    step is new."""
    t0 = time.perf_counter()
    loss = _fence(step(batch))
    return loss, time.perf_counter() - t0


def phase_train(size: Size, require_tpu: bool) -> dict:
    """Returns the first build's seconds and cache events for the cache phase."""
    import jax
    import numpy as np

    from autodist_tpu.training import train

    n_dev = len(jax.devices())
    batch_size = size.micro_batch * n_dev * size.accum
    with _CacheEvents() as first_build:
        _, params, batch, step = build_flagship(size, batch_size, size.accum)
        runner = step.runner
        batch = runner.shard_batch(batch)   # device-resident: the chip, not the link
        first_loss, compile_s = _first_step(step, batch)
    losses = [first_loss, _fence(step(batch))]
    t0 = time.perf_counter()
    for _ in range(size.steps):
        loss = step(batch)
        losses.append(loss)
    losses[2:] = [_fence(x) for x in losses[2:]]
    step_s = (time.perf_counter() - t0) / size.steps

    # runner.py's cost probe re-lowers the step after its first dispatch on
    # the assumption that this hits jit's executable cache: time it.
    t0 = time.perf_counter()
    text = runner.compiled_step(step.get_state(), batch).as_text()
    relower_s = time.perf_counter() - t0
    mosaic = "tpu_custom_call" in text

    # The loop users call, default telemetry, same runner (no recompile).
    loop_losses = []
    final = train(runner, params, lambda i: batch, steps=size.loop_steps,
                  log_every=1,
                  on_metrics=lambda i, loss, rate: loop_losses.append(float(loss)))
    loop_steps_done = int(final.step)

    stats = jax.devices()[0].memory_stats() or {}
    _emit({"phase": "train", "losses": [round(x, 4) for x in losses],
           "train_loop_losses": [round(x, 4) for x in loop_losses],
           "compile_and_first_step_s": round(compile_s, 2),
           "step_s": round(step_s, 4),
           "tokens_per_step": batch_size * size.seq_len,
           "relower_after_dispatch_s": round(relower_s, 3),
           "tpu_custom_call_in_step": mosaic,
           "peak_bytes_in_use": stats.get("peak_bytes_in_use")})
    if not np.all(np.isfinite(losses + loop_losses)):
        raise SmokeFailure(f"non-finite loss: {losses} / {loop_losses}")
    if not losses[-1] < losses[0]:
        raise SmokeFailure(f"loss did not fall: {losses}")
    if loop_steps_done != size.loop_steps or not loop_losses:
        raise SmokeFailure(f"train() ran {loop_steps_done} of "
                           f"{size.loop_steps} steps and logged {loop_losses}")
    if require_tpu and not mosaic:
        raise SmokeFailure("no tpu_custom_call in the compiled step: the "
                           "fused head did not run as a Mosaic kernel")
    return {"seconds": compile_s, "hits": first_build.hits,
            "misses": first_build.misses}


def _rel_err(got, ref) -> float:
    import jax.numpy as jnp
    got, ref = got.astype(jnp.float32), ref.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))


def phase_kernels(size: Size) -> None:
    """Both Pallas kernels, forward and backward, executed on the device and
    compared with plain float32 references. The flagship uses dot attention
    at seq 256, so this is where the flash kernel runs on the chip."""
    import jax
    import jax.numpy as jnp

    from autodist_tpu.models.transformer_lm import (causal_mask,
                                                    dot_product_attention)
    from autodist_tpu.ops import flash_attention, fused_softmax_xent

    keys = jax.random.split(jax.random.PRNGKey(SEED), 7)
    f32 = jnp.float32

    def out_and_grads(fn, *args):
        """[out, *d(out . cot)/d(args)] of ``fn(*args) -> (scalar, out)``."""
        (_, out), grads = jax.jit(jax.value_and_grad(
            fn, argnums=tuple(range(len(args))), has_aux=True))(*args)
        return [out, *grads]

    b, length, h, d = size.flash_shape
    q, k, v, cot = (jax.random.normal(key, (b, length, h, d), jnp.bfloat16)
                    for key in keys[:4])

    def flash(q, k, v):
        out = flash_attention(q, k, v, causal=True)
        return jnp.sum(out.astype(f32) * cot.astype(f32)), out

    def dot(q, k, v):
        out = dot_product_attention(q, k, v, causal_mask(length, f32), f32)
        return jnp.sum(out * cot.astype(f32)), out

    n, dm, vocab = size.xent_rows, size.d_model, size.vocab_size
    hid = jax.random.normal(keys[4], (n, dm), jnp.bfloat16)
    table = jax.random.normal(keys[5], (dm, vocab), f32) * dm ** -0.5
    targets = jax.random.randint(keys[6], (n,), 0, vocab)

    def fused(hid, table):
        nll = fused_softmax_xent(hid, table, targets)
        return nll.mean(), nll

    def plain(hid, table):
        logp = jax.nn.log_softmax(hid.astype(f32) @ table, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
        return nll.mean(), nll

    got = out_and_grads(flash, q, k, v) + out_and_grads(fused, hid, table)
    with jax.default_matmul_precision("highest"):
        ref = (out_and_grads(dot, *(x.astype(f32) for x in (q, k, v)))
               + out_and_grads(plain, hid, table))
    names = ("flash_out", "flash_dq", "flash_dk", "flash_dv",
             "xent_nll", "xent_dh", "xent_dw")
    errs = {name: _rel_err(g, r) for name, g, r in zip(names, got, ref)}

    _emit({"phase": "kernels", "flash_shape": list(size.flash_shape),
           "xent_shape": [n, dm, vocab], "tolerance": KERNEL_TOL,
           "rel_err": {k: round(e, 5) for k, e in errs.items()}})
    bad = {k: e for k, e in errs.items() if not e <= KERNEL_TOL}
    if bad:
        raise SmokeFailure(f"kernels disagree with their references: {bad}")


def phase_cache(size: Size, first: dict, require_tpu: bool) -> None:
    """Build the same step again after ``jax.clear_caches()``: every program
    has to come from the persistent cache, not from the compiler. ``first``
    is the train phase's build: cold where the machine came with an empty
    cache, itself served from the cache where an earlier call filled it."""
    import jax

    cache_dir = jax.config.jax_compilation_cache_dir
    n_dev = len(jax.devices())
    jax.clear_caches()
    with _CacheEvents() as second:
        _, _, batch, step = build_flagship(
            size, size.micro_batch * n_dev * size.accum, size.accum)
        loss, warm_s = _first_step(step, step.runner.shard_batch(batch))
    _emit({"phase": "cache", "compile_cache_dir": cache_dir,
           "first_build": {"compile_and_first_step_s": round(first["seconds"], 2),
                           "cache_hits": first["hits"],
                           "cache_misses": first["misses"]},
           "second_build": {"compile_and_first_step_s": round(warm_s, 2),
                            "cache_hits": second.hits,
                            "cache_misses": second.misses},
           "loss": round(loss, 4)})
    if not require_tpu:
        return   # the CPU backend keeps no persistent cache, by design
    if not cache_dir:
        raise SmokeFailure("no persistent compile cache directory is set")
    if second.hits == 0 or second.misses > 0:
        raise SmokeFailure(
            f"second build was not served from {cache_dir}: "
            f"{second.hits} hits, {second.misses} misses")
    if first["misses"] and not warm_s < first["seconds"]:
        raise SmokeFailure(
            f"second build ({warm_s:.1f}s) is no faster than the first, which "
            f"compiled ({first['seconds']:.1f}s)")


def phase_sharded(size: Size, chips: int) -> None:
    """The sharded step against one device: same seed, same global batch.
    Two steps each, so the second loss has been through the gradient
    collective and the sharded update."""
    import jax
    import optax

    from autodist_tpu import ResourceSpec
    from autodist_tpu.model_spec import ModelSpec
    from autodist_tpu.parallel.mesh import single_device_mesh
    from autodist_tpu.parallel.plan import ShardingPlan
    from autodist_tpu.runner import DistributedRunner
    from autodist_tpu.strategy import AllReduce, PartitionedPS

    _, params, loss_fn, batch = flagship_model(size, size.sharded_batch)
    spec_model = ModelSpec.from_loss_fn(loss_fn, params, batch)
    one = ResourceSpec(resource_info={
        "nodes": [{"address": "localhost", "tpus": 1, "chief": True}]})
    strategy = AllReduce().build(spec_model, one)
    single = DistributedRunner(
        strategy, spec_model, loss_fn, optax.adam(1e-3),
        mesh=single_device_mesh(),
        plan=ShardingPlan.from_strategy(strategy, spec_model))
    state = single.init(params)
    ref = []
    for _ in range(2):
        state, loss = single.run(state, batch)
        ref.append(_fence(loss))
    del state
    _emit({"phase": "sharded", "case": "single_device",
           "losses": [round(x, 5) for x in ref],
           "global_batch": size.sharded_batch})

    for name, builder, axes in (
            (f"AllReduce data={chips}", AllReduce(), {"data": chips}),
            (f"PartitionedPS model=2 data={chips // 2}", PartitionedPS(),
             {"model": 2, "data": chips // 2})):
        spec = ResourceSpec(resource_info={
            "nodes": [{"address": "localhost", "tpus": chips, "chief": True}],
            "mesh": axes})
        _, _, batch, step = build_flagship(size, size.sharded_batch, 1,
                                           strategy=builder, resource_spec=spec)
        runner = step.runner
        sharded = runner.shard_batch(batch)
        losses = [_fence(step(sharded)), _fence(step(sharded))]
        state = step.get_state()
        text = runner.compiled_step(state, sharded).as_text()
        collectives = [c for c in ("all-reduce", "reduce-scatter", "all-gather")
                       if c in text]
        spans = sorted({len(leaf.sharding.device_set)
                        for leaf in jax.tree_util.tree_leaves(state.params)})
        rel = [abs(a - b) / abs(b) for a, b in zip(losses, ref)]
        _emit({"phase": "sharded", "case": name,
               "mesh": {k: v for k, v in runner.mesh.shape.items() if v > 1},
               "losses": [round(x, 5) for x in losses],
               "rel_diff_vs_single": [round(r, 7) for r in rel],
               "tolerance": SHARDED_LOSS_RTOL, "collectives": collectives,
               "param_device_set_sizes": spans,
               "tpu_custom_call_in_step": "tpu_custom_call" in text})
        if runner.mesh.size != chips:
            raise SmokeFailure(f"{name}: mesh spans {runner.mesh.size} devices")
        if max(rel) > SHARDED_LOSS_RTOL:
            raise SmokeFailure(f"{name}: losses {losses} differ from the "
                               f"single-device {ref} by {rel}")
        if not {"all-reduce", "reduce-scatter"} & set(collectives):
            raise SmokeFailure(f"{name}: no gradient collective in the step")
        if spans != [chips]:
            raise SmokeFailure(f"{name}: parameters span {spans} devices, "
                               f"expected {chips} for every one")


# ---------------------------------------------------------------------- run

def run(size: Size = FLAGSHIP, chips: int = 1, require_tpu: bool = True) -> int:
    """Run the phases for ``chips`` devices; returns the exit code. The last
    line printed is the result the driver reads."""
    phase, device = "device", None
    try:
        device = describe_device()
        phase_device(device, require_tpu, chips)
        if chips == 1:
            phase = "native"
            phase_native()
            phase = "train"
            first_build = phase_train(size, require_tpu)
            phase = "kernels"
            phase_kernels(size)
            phase = "cache"
            phase_cache(size, first_build, require_tpu)
        else:
            phase = "sharded"
            phase_sharded(size, chips)
    except Exception as e:  # noqa: BLE001 — the boundary: report, then fail
        traceback.print_exc()
        _emit({"ok": False, "phase": phase,
               "error": f"{type(e).__name__}: {e}", "device": device})
        return 1
    _emit({"ok": True, "device": device})
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="1 (default): the whole smoke on one chip; 4: "
                             "only the sharded step and its single-device "
                             "reference, on a four-chip host")
    args = parser.parse_args(argv)
    return run(FLAGSHIP, chips=args.chips)


if __name__ == "__main__":
    sys.exit(main())
