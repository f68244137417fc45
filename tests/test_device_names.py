"""Names a device trace can read, spans over the log boundary, and set-up
seconds booked by the program itself.

- every Pallas kernel carries its name (``ops/named_call.py``) and the four
  phases of a step sit under ``jax.named_scope``s; names are trace-time
  metadata, so the lowered StableHLO without locations equals the unnamed
  step's;
- ``train.boundary`` appears once per log boundary, whether a dispatch is a
  step or a block, and nests ``train.boundary.planes`` and
  ``train.boundary.on_metrics``; its stages keep their order;
- ``setup.*`` and ``jit.*`` counters are booked with telemetry off, from
  ``jax.monitoring`` listeners registered once however often ``configure()``
  runs, nested traces counted once.

Pure in-process tests on the CPU mesh (kernels in interpret mode).
"""

import collections
import contextlib
import functools
import importlib
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu import AutoDist, telemetry, train
from autodist_tpu.models import transformer_lm
from autodist_tpu.ops import named_call
from autodist_tpu.strategy import AllReduce
from autodist_tpu.utils import compile_cache

PHASES = ("step.grad", "step.accumulate", "step.grad_sync", "step.optimizer")


@pytest.fixture(autouse=True)
def _telemetry_reset():
    telemetry.disable()
    telemetry.clear()
    yield
    telemetry.disable()
    telemetry.clear()


# ------------------------------------------------------------ lowered steps

def _lm_step_lowered(zero=0, accum=2, vocab=203):
    """The tiny flagship step (flash attention, fused head, accumulation)
    through ``AutoDist`` on the 8-device mesh, lowered and not compiled."""
    cfg = transformer_lm.TransformerLMConfig(
        vocab_size=vocab, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_len=16, dtype=jnp.float32, attention_impl="flash",
        fused_head=True)
    model, params = transformer_lm.init_params(cfg, rng=jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": rng.integers(0, vocab, (16, 17)).astype(np.int32)}
    runner = AutoDist(strategy_builder=AllReduce()).create_distributed_session(
        transformer_lm.make_loss_fn(model), params, optax.adam(1e-3),
        example_batch=batch, accumulation_steps=accum, zero=zero)
    state = runner.init(params)
    with runner.mesh:
        return runner._build_step(None).lower(state,
                                              runner.shard_batch(batch))


def _scopes(text: str, names) -> set:
    """The names that are a scope of some location: a whole component of a
    name stack with something below it (``_flash_fwd`` the function is not
    ``flash_fwd`` the scope)."""
    return {n for n in names if re.search(r'[/"]' + re.escape(n) + "/", text)}


@pytest.mark.parametrize("backward", ["one-pass", "split"])
def test_lowered_step_names_kernels_and_phases(backward, monkeypatch):
    """The flash backward is one kernel, ``flash_bwd_dkv``, while dQ of a
    (batch, head) stays in VMEM (this shape); ``flash_bwd_dq`` is the second
    kernel of the split path, forced here through the byte limit."""
    if backward == "split":
        fa = importlib.import_module("autodist_tpu.ops.flash_attention")
        monkeypatch.setattr(fa, "_RESIDENT_DQ_BYTES", 0)
    text = _lm_step_lowered(zero=1).as_text(debug_info=True)
    step_kernels = [k for k in named_call.KERNEL_NAMES
                    if k != "flash_carry"
                    and not k.startswith(("moe_", "short_conv_", "ssd_",
                                          "conv_silu_", "selective_scan_",
                                          "flash_sink_", "gated_norm_", "eva_",
                                          "kda_"))
                    and (k != "flash_bwd_dq" or backward == "split")]
    assert _scopes(text, named_call.KERNEL_NAMES) == set(step_kernels)
    # ZeRO's constrain_update is the reduction under AllReduce (the implicit
    # lowering leaves the all-reduce to XLA, so it has no scope of its own).
    assert _scopes(text, PHASES) == set(PHASES)


@pytest.mark.parametrize("vocab,head", [(1100, "one-pass"), (203, "two-kernels")])
def test_lowered_step_names_the_head_backward_in_both_paths(vocab, head):
    """The fused head's backward is one kernel, ``xent_bwd_dw`` (dh, dw and
    db from one logits tile), from three vocab blocks up (1,100 words in
    blocks of 512); ``xent_bwd_dh`` is the first of the two kernels a
    smaller vocabulary keeps (203 words: one block)."""
    text = _lm_step_lowered(vocab=vocab).as_text(debug_info=True)
    head_kernels = {k for k in named_call.KERNEL_NAMES if k.startswith("xent_")}
    assert _scopes(text, head_kernels) == head_kernels - (
        {"xent_bwd_dh"} if head == "one-pass" else set())


def test_explicit_gradient_sync_sits_under_its_scope_inside_step_grad():
    """A compressor takes ``make_grad_fn``'s explicit lowering: the
    reduction is under ``step.grad_sync``, nested in ``step.grad``."""
    params = {"w": np.ones((8, 4), np.float32), "b": np.zeros((4,), np.float32)}
    batch = {"x": np.ones((16, 8), np.float32), "y": np.ones((16, 4), np.float32)}
    loss = lambda p, b: jnp.mean((b["x"] @ p["w"] + p["b"] - b["y"]) ** 2)  # noqa: E731
    runner = AutoDist(strategy_builder=AllReduce(compressor="HorovodCompressor")) \
        .create_distributed_session(loss, params, optax.sgd(0.1),
                                    example_batch=batch)
    state = runner.init(params)
    with runner.mesh:
        text = runner._build_step(None).lower(
            state, runner.shard_batch(batch)).as_text(debug_info=True)
    # The shard_map's body is a function of its own in the lowered module,
    # with its own name stack: the call sits under ``step.grad`` and the
    # collectives inside the body under ``step.grad_sync``.
    assert "step.grad/shard_map" in text and "step.optimizer" in text
    assert "step.grad_sync/psum" in text
    backward = [line for line in text.splitlines() if "transpose(" in line]
    assert backward and not any("step.grad_sync" in l for l in backward)


MOE_SCOPES = ("moe.route", "moe.dispatch", "moe.experts", "moe.combine")


def _olmoe_step_lowered():
    """The tiny OLMoE step (QK-norm + RoPE attention, dropless top-2 of 8
    experts, fused head) through ``AutoDist`` on the 8-device mesh."""
    from autodist_tpu.models import olmoe
    cfg = olmoe.OlmoeConfig(
        vocab_size=203, d_model=32, n_heads=2, n_layers=1, d_expert=16,
        n_experts=8, top_k=2, max_len=16, dtype=jnp.float32,
        attention_impl="flash", fused_head=True)
    model, params = olmoe.init_params(cfg, rng=jax.random.PRNGKey(0))
    batch = olmoe.synthetic_batch(cfg, batch_size=16, seq_len=16)
    runner = AutoDist(strategy_builder=AllReduce()).create_distributed_session(
        olmoe.make_loss_fn(model), params, optax.adam(1e-3),
        example_batch=batch)
    state = runner.init(params)
    with runner.mesh:
        return runner._build_step(None).lower(state,
                                              runner.shard_batch(batch))


@functools.lru_cache(maxsize=1)
def _olmoe_step_text() -> str:       # one lowering for the seven cases
    return _olmoe_step_lowered().as_text(debug_info=True)


@pytest.mark.parametrize("name", [k for k in named_call.KERNEL_NAMES
                                  if k.startswith("moe_gmm_")] + list(MOE_SCOPES))
def test_olmoe_step_names_its_kernels_and_routing_scopes(name):
    """The grouped-matmul kernels by their device names, and the four scopes
    of the routed FFN innermost around their operations."""
    assert _scopes(_olmoe_step_text(), [name]) == {name}


def test_moe_gauges_are_set_when_the_layer_is_traced():
    _olmoe_step_lowered()
    assert telemetry.gauge("moe.experts").value == 8
    assert telemetry.gauge("moe.top_k").value == 2
    # per device: 16 sequences of 16 over 8 devices, two slots a token
    assert telemetry.gauge("moe.rows_per_call").value == 2 * 16 * 2
    assert telemetry.gauge("moe.gmm.row_tiles").value == 1 + 8


CONV_SCOPES = ("conv.in_proj", "conv.gate_conv", "conv.out_proj")


@functools.lru_cache(maxsize=1)
def _lfm2_step_text() -> str:       # one lowering for the cases below
    """The tiny LFM2-MoE step (a conv layer behind a dense MLP, an attention
    layer and a conv layer with their share of the experts, the tied fused
    head) through ``AutoDist`` on the 8-device mesh."""
    from autodist_tpu.models import lfm2_moe
    cfg = lfm2_moe.Lfm2MoeConfig(
        vocab_size=203, d_model=128, n_heads=2, n_kv_heads=1, head_dim=16,
        layer_types=("conv", "full_attention", "conv"), n_dense_layers=1,
        d_ff=64, d_expert=16, n_experts_routed=8, experts_held=2,
        first_expert_held=2, top_k=2, max_len=16, dtype=jnp.float32,
        attention_impl="flash", conv_impl="pallas", fused_head=True)
    model, params = lfm2_moe.init_params(cfg, rng=jax.random.PRNGKey(0))
    batch = lfm2_moe.synthetic_batch(cfg, batch_size=16, seq_len=16)
    runner = AutoDist(strategy_builder=AllReduce()).create_distributed_session(
        lfm2_moe.make_loss_fn(model), params,
        lfm2_moe.make_optimizer(1e-3, cfg.load_balance_coeff),
        example_batch=batch)
    state = runner.init(params)
    with runner.mesh:
        return runner._build_step(None).lower(
            state, runner.shard_batch(batch)).as_text(debug_info=True)


@pytest.mark.parametrize("name", [k for k in named_call.KERNEL_NAMES
                                  if k.startswith("short_conv_")]
                         + ["moe_rows_combine"]
                         + list(CONV_SCOPES) + list(MOE_SCOPES))
def test_lfm2_step_names_its_conv_kernels_and_scopes(name):
    """The gated short convolution's two kernels by their device names
    (``pallas:short_conv_fwd`` / ``pallas:short_conv_bwd`` in a trace), the
    three scopes of the conv operator, and the routed share's beside them
    with the kernel that adds a token's rows up (``pallas:moe_rows_combine``:
    the combine and the dispatch's transpose)."""
    assert _scopes(_lfm2_step_text(), [name]) == {name}


def test_a_shares_gathers_stay_xlas_and_the_whole_bank_names_no_row_kernel():
    """``moe_rows_gather`` is in no step: the share's two gathers run at the
    chip's bandwidth as XLA's (PERF.md §6, PR 34), and OLMoE's whole bank
    moves permutations, which have no rows to add up."""
    rows = [k for k in named_call.KERNEL_NAMES if k.startswith("moe_rows_")]
    assert _scopes(_lfm2_step_text(), rows) == {"moe_rows_combine"}
    assert _scopes(_olmoe_step_text(), rows) == set()


SSM_SCOPES = ("ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.gate_norm",
              "ssm.out_proj", "moe.shared")


@functools.lru_cache(maxsize=1)
def _nemotron_step_text() -> str:       # one lowering for the cases below
    """The tiny Nemotron-H step (a Mamba-2 layer, an expert layer with its
    share of the relu2 experts, an attention layer, a Mamba-2 layer, every
    layer recomputed, the fused untied head) through ``AutoDist`` on the
    8-device mesh."""
    from autodist_tpu.models import nemotron_h
    cfg = nemotron_h.NemotronHConfig(
        vocab_size=203, d_model=128, pattern="ME*M", mamba_heads=2,
        mamba_head_dim=64, n_groups=1, d_state=128, n_heads=2, n_kv_heads=1,
        head_dim=16, d_expert=16, d_shared=32, n_experts_routed=8,
        experts_held=2, first_expert_held=2, top_k=2, max_len=16,
        dtype=jnp.float32, attention_impl="flash", ssm_impl="pallas",
        fused_head=True, remat=True)
    model, params = nemotron_h.init_params(cfg, rng=jax.random.PRNGKey(0))
    batch = nemotron_h.synthetic_batch(cfg, batch_size=16, seq_len=16)
    runner = AutoDist(strategy_builder=AllReduce()).create_distributed_session(
        nemotron_h.make_loss_fn(model), params,
        nemotron_h.make_optimizer(1e-3, cfg.load_balance_coeff),
        example_batch=batch)
    state = runner.init(params)
    with runner.mesh:
        return runner._build_step(None).lower(
            state, runner.shard_batch(batch)).as_text(debug_info=True)


@pytest.mark.parametrize("name", [k for k in named_call.KERNEL_NAMES
                                  if k.startswith(("ssd_", "conv_silu_",
                                                   "gated_norm_"))]
                         + ["moe_rows_combine", "moe_gmm_fwd", "flash_fwd",
                            "xent_fwd"] + list(SSM_SCOPES) + list(MOE_SCOPES))
def test_nemotron_step_names_its_scan_kernels_and_scopes(name):
    """The chunked scan's two kernels and the two of the convolution before it
    by their device names (``pallas:ssd_fwd`` / ``pallas:ssd_bwd``,
    ``pallas:conv_silu_fwd`` / ``pallas:conv_silu_bwd`` in a trace), the five
    scopes of the Mamba-2 layer and
    the shared expert's, beside the routed share's four and the kernels the
    step shares with the other families, under per-layer recomputation."""
    assert _scopes(_nemotron_step_text(), [name]) == {name}


def test_ssd_and_expert_form_gauges_are_set_when_the_step_is_traced():
    calls = telemetry.counter("ssd.calls").value
    _nemotron_step_text.cache_clear()
    _nemotron_step_text()
    # the call as the model makes it, all devices: 16 sequences of 16
    # positions, each padded to one chunk of 128; float32 operands here
    assert [telemetry.gauge(f"ssd.{k}").value for k in
            ("chunk", "chunks", "heads", "groups", "state")] == [128, 16, 2, 1, 128]
    wide, narrow = 16 * 16 * 2 * 64 * 4, 16 * 16 * 128 * 4
    states = 16 * 2 * 64 * 128 * 4
    assert telemetry.gauge("ssd.fwd.bytes").value == 2 * wide + 2 * narrow + states
    assert telemetry.gauge("ssd.bwd.bytes").value == 3 * wide + 4 * narrow + states
    assert telemetry.counter("ssd.calls").value >= calls + 2    # two Mamba-2 layers
    assert telemetry.gauge("moe.expert_form").value == 2        # relu2: up, down
    _olmoe_step_lowered()
    assert telemetry.gauge("moe.expert_form").value == 3        # gate, up, down


JAMBA_SCOPES = ("jamba.mamba", "jamba.attention", "jamba.mlp", "ssm.in_proj",
                "ssm.conv", "ssm.x_proj", "ssm.scan", "ssm.out_proj")


@functools.lru_cache(maxsize=1)
def _jamba_step_text() -> str:       # one lowering for the cases below
    """The tiny Jamba step (a Mamba-1 layer, the attention layer, every layer
    recomputed, the tied fused head) through ``AutoDist`` under
    ``FullySharded`` on the 8-device mesh: 1,024 channels, the narrowest the
    scan's kernels take."""
    from autodist_tpu.models import jamba
    from autodist_tpu.strategy import FullySharded
    cfg = jamba.JambaConfig(
        vocab_size=256, d_model=512, n_layers=2, attn_period=2, attn_offset=1,
        d_state=16, dt_rank=8, n_heads=4, n_kv_heads=1, d_ff=64, max_len=64,
        dtype=jnp.float32, chunk=64, attention_impl="flash", ssm_impl="pallas",
        fused_head=True, remat=True)
    model, params = jamba.init_params(cfg, rng=jax.random.PRNGKey(0))
    batch = jamba.synthetic_batch(cfg, batch_size=8, seq_len=64)
    import optax
    runner = AutoDist(strategy_builder=FullySharded()) \
        .create_distributed_session(jamba.make_loss_fn(model), params,
                                    optax.adamw(1e-3), example_batch=batch)
    state = runner.init(params)
    with runner.mesh:
        return runner._build_step(None).lower(
            state, runner.shard_batch(batch)).as_text(debug_info=True)


@pytest.mark.parametrize("name", [k for k in named_call.KERNEL_NAMES
                                  if k.startswith("selective_scan_")]
                         + ["conv_silu_fwd", "conv_silu_bwd", "flash_fwd",
                            "xent_fwd", "step.grad_sync"] + list(JAMBA_SCOPES))
def test_jamba_step_names_its_scan_kernels_and_scopes(name):
    """The selective scan's two kernels by their device names
    (``pallas:selective_scan_fwd`` / ``pallas:selective_scan_bwd`` in a
    trace), the convolution's before them, the three spans of a Jamba layer
    and the five scopes of its Mamba-1 mixer, beside the kernels the step
    shares with the other families; with the state stored as shares the
    gradient's landing on them sits under ``step.grad_sync``."""
    assert _scopes(_jamba_step_text(), [name]) == {name}


def test_selective_scan_and_sharding_gauges_are_set_when_the_step_is_traced():
    calls = telemetry.counter("selective_scan.calls").value
    _jamba_step_text.cache_clear()
    _jamba_step_text()
    # the call as the model makes it, all devices: 8 sequences of 64
    assert [telemetry.gauge(f"selective_scan.{k}").value for k in
            ("chunk", "chunks", "channels", "state")] == [64, 8, 1024, 16]
    assert telemetry.counter("selective_scan.calls").value >= calls + 1
    # the leaves of 2^18 elements or more, float32, 7/8 of each a device:
    # in_proj 512 x 2,048, out_proj 1,024 x 512, q and o 512 x 512 (the table
    # 256 x 512 and k and v 512 x 128 are under it, and whole)
    stored = 512 * 2048 + 1024 * 512 + 2 * 512 * 512
    assert telemetry.gauge("step.param_gather_bytes").value == stored * 4 * 7 // 8
    assert telemetry.gauge("step.grad_scatter_bytes").value == stored * 4 * 7 // 8


MIMO_SCOPES = ("attn.rope_partial", "attn.sink_grad", "moe.route",
               "moe.dispatch", "moe.experts", "moe.combine")


@functools.lru_cache(maxsize=1)
def _mimo_step_text() -> str:       # one lowering for the cases below
    """The tiny MiMo-V2 step (the dense full layer, a sliding expert layer
    with its sinks, every layer recomputed, the fused head) through
    ``AutoDist`` under ``FullySharded`` on the 8-device mesh; the banks (8 x
    256 x 128) are stored as eighths."""
    from autodist_tpu.models import mimo_v2
    from autodist_tpu.strategy import FullySharded
    cfg = mimo_v2.MimoV2Config(
        vocab_size=256, d_model=256, n_heads=4, n_kv_heads=1, swa_n_kv_heads=2,
        head_dim=48, v_head_dim=32, layer_pattern=(0, 1), moe_layer_freq=(0, 1),
        d_ff=64, d_expert=128, n_experts_routed=16, experts_held=8, top_k=2,
        rows_bound=16, window=16, max_len=64, dtype=jnp.float32,
        attention_impl="flash", fused_head=True, remat=True)
    model, params = mimo_v2.init_params(cfg, rng=jax.random.PRNGKey(0))
    batch = mimo_v2.synthetic_batch(cfg, batch_size=8, seq_len=32)
    runner = AutoDist(strategy_builder=FullySharded()) \
        .create_distributed_session(
            mimo_v2.make_loss_fn(model), params,
            mimo_v2.make_optimizer(1e-3, cfg.load_balance_coeff),
            example_batch=batch)
    state = runner.init(params)
    with runner.mesh:
        return runner._build_step(None).lower(
            state, runner.shard_batch(batch)).as_text(debug_info=True)


@pytest.mark.parametrize("name", [k for k in named_call.KERNEL_NAMES
                                  if k.startswith("flash_sink_")
                                  and k != "flash_sink_bwd_dq"]
                         + ["flash_fwd", "flash_bwd_dkv", "moe_gmm_fwd",
                            "xent_fwd", "step.grad_sync"] + list(MIMO_SCOPES))
def test_mimo_step_names_its_sink_kernels_and_scopes(name):
    """A sliding layer's flash kernels by their own device names
    (``pallas:flash_sink_fwd`` / ``pallas:flash_sink_bwd_dkv`` in a trace)
    beside the full layer's plain ones, the partial rotary turn and the
    sinks' gradient as scopes, and the share's, with the state stored as
    shares."""
    assert _scopes(_mimo_step_text(), [name]) == {name}


def test_sink_and_band_gauges_are_set_when_the_step_is_traced():
    _mimo_step_text()       # traced by the cases above, or here when run alone
    assert telemetry.gauge("attn.sink_layers").value == 1
    # 8 sequences x 4 heads x one sliding layer: 32 queries see 16 keys at
    # most, and the one 32 x 32 tile a head is computed whole
    assert telemetry.gauge("attn.band_pairs_visible").value == \
        32 * (16 * 17 // 2 + 16 * 16)
    assert telemetry.gauge("attn.band_pairs_computed").value == 32 * 32 * 32
    assert telemetry.gauge("moe.experts_held").value == 8
    assert telemetry.gauge("moe.router_width").value == 16


@functools.lru_cache(maxsize=1)
def _evabyte_step_text() -> str:    # one lowering for the cases below
    from autodist_tpu.models import evabyte
    cfg = evabyte.EvaByteConfig(
        vocab_size=40, d_model=64, n_layers=2, n_heads=4, heads_held=2,
        head_dim=16, d_ff=96, window=32, chunk=4, n_pred_heads=3, max_len=128,
        dtype=jnp.float32, attention_impl="kernel", remat=True)
    model, params = evabyte.init_params(cfg, rng=jax.random.PRNGKey(0))
    batch = evabyte.synthetic_batch(cfg, batch_size=8, seq_len=64)
    runner = AutoDist(strategy_builder=AllReduce()).create_distributed_session(
        evabyte.make_loss_fn(model), params, optax.adamw(1e-3),
        example_batch=batch)
    state = runner.init(params)
    with runner.mesh:
        return runner._build_step(None).lower(
            state, runner.shard_batch(batch)).as_text(debug_info=True)


@pytest.mark.parametrize("name", ["eva_fwd", "eva_bwd", "eva_pool", "attn.rope"])
def test_evabyte_step_names_its_eva_kernels_and_the_pooling_scope(name):
    """EVA's two kernels by their own device names (``pallas:eva_fwd`` /
    ``pallas:eva_bwd`` in a trace, apart from other cells' ``flash_*``) and
    the pooling of the summaries as a scope of its own."""
    text = _evabyte_step_text()
    assert _scopes(text, [name]) == {name}
    assert _scopes(text, named_call.KERNEL_NAMES) == {"eva_fwd", "eva_bwd"}


def test_eva_gauges_are_set_when_the_step_is_traced():
    _evabyte_step_text()    # traced by the cases above, or here when run alone
    # per device: 8 sequences of 64 over 8 devices, two windows of 32
    assert telemetry.gauge("eva.windows").value == 2
    assert telemetry.gauge("eva.summaries").value == 16
    assert telemetry.gauge("attention.heads_held").value == 2
    assert telemetry.gauge("loss.pred_heads").value == 3
    # 8 sequences x 2 heads held x 2 layers; a query sees its window's keys
    # up to itself and window 0's 8 summaries from window 1
    pairs = 2 * (32 * 33 // 2) + 32 * 8
    assert telemetry.gauge("eva.pairs.visible").value == 32 * pairs
    # the one 32 x 32 tile a window whole, and window 1's 8 x 32 of summaries
    assert telemetry.gauge("eva.pairs.computed").value == 32 * (
        2 * 32 * 32 + 32 * 8)


@functools.lru_cache(maxsize=1)
def _ling_step_text() -> str:       # one lowering for the cases below
    """A tiny Ling / Ring hybrid step with every kernel option: a KDA layer
    (heads of 128, the kernels' width) and a gated latent layer, one of two
    heads held, a grouped router over a share."""
    from autodist_tpu.models import bailing_hybrid
    cfg = bailing_hybrid.BailingHybridConfig(
        vocab_size=64, d_model=32, n_layers=2, layer_group_size=2, n_heads=2,
        heads_held=1, first_head_held=1, head_dim=128, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, kv_lora_rank=12, n_dense_layers=1,
        d_ff=48, d_expert=16, d_shared=16, n_experts_routed=8, experts_held=2,
        top_k=2, n_group=4, topk_group=2, rows_bound=32, max_len=128,
        dtype=jnp.float32, attention_impl="flash", kda_impl="pallas",
        fused_head=True, remat=True)
    model, params = bailing_hybrid.init_params(cfg, rng=jax.random.PRNGKey(0))
    batch = bailing_hybrid.synthetic_batch(cfg, batch_size=8, seq_len=64)
    runner = AutoDist(strategy_builder=AllReduce()).create_distributed_session(
        bailing_hybrid.make_loss_fn(model), params,
        bailing_hybrid.make_optimizer(1e-3, cfg.load_balance_coeff),
        example_batch=batch)
    state = runner.init(params)
    with runner.mesh:
        return runner._build_step(None).lower(
            state, runner.shard_batch(batch)).as_text(debug_info=True)


@pytest.mark.parametrize("name", ["kda_fwd", "kda_bwd", "conv_silu_fwd",
                                  "conv_silu_bwd", "kda_qk_norm", "kda_gate",
                                  "kda_out_norm", "mla.head_gate", "moe.route"])
def test_ling_step_names_its_kda_kernels_and_scopes(name):
    """The recurrence's two kernels by their own device names
    (``pallas:kda_fwd`` / ``pallas:kda_bwd`` in a trace) and what XLA keeps of
    the mixer between the projections and the kernels under scopes of its
    own: the two L2 norms, the decay and beta, the output norm under its
    gate."""
    text = _ling_step_text()
    assert _scopes(text, [name]) == {name}
    assert _scopes(text, named_call.KERNEL_NAMES) >= {
        "kda_fwd", "kda_bwd", "conv_silu_fwd", "conv_silu_bwd", "flash_fwd",
        "flash_bwd_dkv", "xent_fwd", "moe_gmm_fwd"}
    assert not _scopes(text, ["ssd_fwd", "eva_fwd", "gated_norm_fwd"])


def test_kda_gauges_are_set_when_the_step_is_traced():
    _ling_step_text()       # traced by the cases above, or here when run alone
    # of the call, all devices: 8 sequences of 64, one chunk of 64 each
    assert telemetry.gauge("kda.chunks").value == 8
    assert telemetry.gauge("kda.chunk").value == 64
    assert telemetry.gauge("kda.heads_held").value == 1
    assert telemetry.gauge("kda.state_kept_bytes").value == 8 * 128 * 128 * 4
    assert telemetry.gauge("attention.heads_held").value == 1
    assert telemetry.gauge("moe.route.groups").value == 4
    assert telemetry.gauge("moe.route.groups_kept").value == 2
    assert telemetry.gauge("moe.experts_held").value == 2
    assert telemetry.gauge("moe.router_width").value == 8
    assert telemetry.gauge("remat.layers").value == 2
    assert telemetry.gauge("remat.kept_bytes").value > 0


def test_the_split_backward_of_a_sink_call_is_named_flash_sink_bwd_dq(monkeypatch):
    fa = importlib.import_module("autodist_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "_RESIDENT_DQ_BYTES", 0)
    q = jnp.ones((1, 32, 2, 8), jnp.float32)
    text = jax.jit(jax.grad(lambda q, s: fa.flash_attention(
        q, q, q, window=8, sink=s).sum(), argnums=(0, 1))).lower(
            q, jnp.zeros((2,))).as_text(debug_info=True)
    assert _scopes(text, named_call.KERNEL_NAMES) == {
        "flash_sink_fwd", "flash_sink_bwd_dkv", "flash_sink_bwd_dq"}


def test_short_conv_gauges_are_set_when_the_operator_is_traced():
    calls = telemetry.counter("short_conv.calls").value
    _lfm2_step_text.cache_clear()
    _lfm2_step_text()
    # per device: 16 sequences of 16 over 8 devices, one 16-row block each
    assert telemetry.gauge("short_conv.fwd.block_rows").value == 16
    assert telemetry.gauge("short_conv.bwd.block_rows").value == 16
    tensor = 2 * 16 * 128 * 4          # [2 x 16, 128] float32 activations
    assert telemetry.gauge("short_conv.fwd.bytes").value == 4 * tensor + 3 * 128 * 4
    assert telemetry.gauge("short_conv.bwd.bytes").value > 7 * tensor
    assert telemetry.counter("short_conv.calls").value >= calls + 2   # two conv layers


def test_flash_carry_is_named():
    fa = importlib.import_module("autodist_tpu.ops.flash_attention")
    q = jnp.ones((1, 16, 2, 8), jnp.float32)
    text = jax.jit(lambda q: fa.flash_attention_with_carry(q, q, q)) \
        .lower(q).as_text(debug_info=True)
    assert _scopes(text, ["flash_carry"])


def test_an_unlisted_kernel_name_is_refused():
    with pytest.raises(ValueError, match="KERNEL_NAMES"):
        named_call.named_pallas_call("attn", lambda *refs: None)


def test_names_are_metadata_only(monkeypatch):
    """The compiled arithmetic is identical: with locations stripped, the
    named step and the step with every name taken away lower to the same
    StableHLO."""
    named = _lm_step_lowered()
    with_locations = named.as_text(debug_info=True)
    assert _scopes(with_locations, ("step.optimizer", "flash_fwd"))
    assert "step.optimizer" not in named.as_text()

    # The same program with every name taken away: ``jax.named_scope`` a
    # no-op and ``pallas_call`` without its ``name=``.
    real_call = named_call.pl.pallas_call
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    monkeypatch.setattr(
        named_call.pl, "pallas_call",
        lambda kernel, name=None, **kwargs: real_call(kernel, **kwargs))
    bare = _lm_step_lowered()
    assert not _scopes(bare.as_text(debug_info=True),
                       PHASES + named_call.KERNEL_NAMES)
    assert bare.as_text() == named.as_text()


def _gradient_through(what):
    """``(module that names a value, the lowered gradient)`` of one operator
    outside any ``jax.checkpoint``."""
    key = jax.random.PRNGKey(0)
    draw = lambda *shape: jax.random.normal(key, shape, jnp.float32)  # noqa: E731
    if what == "flash":
        from autodist_tpu.ops import flash_attention
        fn = lambda q, k: flash_attention(q, k, k).sum()  # noqa: E731
        args = (draw(1, 16, 4, 8), draw(1, 16, 2, 8))
    elif what == "ssd":
        from autodist_tpu.ops.ssd_scan import ssd_scan
        fn = lambda x, b: ssd_scan(  # noqa: E731
            x, jnp.ones((1, 128, 2)), -jnp.ones(2), b, b, jnp.ones(2),
            impl="pallas").sum()
        args = (draw(1, 128, 2, 64), draw(1, 128, 1, 128))
    elif what == "held_pass":
        from autodist_tpu.models import moe
        share = functools.partial(
            moe.routed_experts, top_k=3, first_expert=2, rows_bound=40,
            form="relu2", route=functools.partial(
                moe.sigmoid_topk_route, route_norm=True, route_scale=2.5,
                route_eps=1e-20))
        scores = jax.nn.sigmoid(draw(64, 8))
        fn = lambda x, up: share(  # noqa: E731
            x, scores, None, up, jnp.swapaxes(up, 1, 2), jnp.zeros(8))[0].sum()
        args = (draw(64, 64), draw(3, 64, 24))
    else:
        from autodist_tpu.models.moe import PlainMLP
        mlp = PlainMLP(16, jnp.float32)
        fn = lambda p, h: mlp.apply({"params": p}, h).sum()  # noqa: E731
        args = (mlp.init(key, draw(2, 8))["params"], draw(2, 8))
    module = {"flash": "ops.flash_attention", "ssd": "ops.ssd_scan",
              "held_pass": "models.moe", "plain_mlp": "models.moe"}[what]
    return (importlib.import_module(f"autodist_tpu.{module}"),
            lambda: jax.jit(jax.grad(fn, argnums=(0, 1))).lower(*args).as_text())


@pytest.mark.parametrize("what", ["flash", "ssd", "held_pass", "plain_mlp"])
def test_a_kept_name_outside_a_checkpoint_lowers_to_nothing(what, monkeypatch):
    """``checkpoint_name`` marks what a caller's ``jax.checkpoint`` may keep
    (``models/nemotron_h.py`` ``KEPT``); where no checkpoint surrounds the
    operator the lowered gradient holds no trace of the name and the
    operations, one by one, of a build without it."""
    from autodist_tpu.models import nemotron_h
    module, lowered = _gradient_through(what)
    named = lowered()
    assert not [name for name in nemotron_h.KEPT if name in named]
    operations = lambda text: collections.Counter(  # noqa: E731
        re.findall(r"= \"?([a-z_]+\.[a-z_.]+)", text))
    assert sum(operations(named).values()) > 10
    monkeypatch.setattr(module, "checkpoint_name", lambda value, name: value)
    assert operations(lowered()) == operations(named)


# ----------------------------------------------------------- log boundaries

def _linear_session():
    params = {"w": np.ones((4, 1), np.float32), "b": np.zeros((1,), np.float32)}
    loss = lambda p, b: jnp.mean((b["y"] - (b["x"] @ p["w"] + p["b"])) ** 2)  # noqa: E731
    batch = {"x": np.ones((32, 4), np.float32), "y": np.ones((32, 1), np.float32)}
    runner = AutoDist(strategy_builder=AllReduce()).create_distributed_session(
        loss, params, optax.sgd(0.01), example_batch=batch)
    return runner, params, batch


def _contained(child, parent) -> bool:
    return (parent[2] <= child[2]
            and child[2] + child[3] <= parent[2] + parent[3])


@pytest.mark.parametrize("unroll", [1, 2], ids=["per_step", "unrolled"])
def test_boundary_span_once_per_log_boundary_with_two_children(unroll):
    runner, params, batch = _linear_session()
    seen = []
    telemetry.enable()
    train(runner, params, lambda i: batch, steps=9, log_every=2,
          unroll=unroll, prefetch_depth=0,
          on_metrics=lambda step, loss, rate: seen.append(step))
    spans = telemetry.snapshot_spans()
    by_name = {}
    for s in spans:
        by_name.setdefault(s[0], []).append(s)
    boundaries = by_name["train.boundary"]
    assert len(seen) >= 3                            # the run had boundaries
    assert len(boundaries) == len(seen)              # one span each, no more
    for child in ("train.boundary.planes", "train.boundary.on_metrics"):
        assert len(by_name[child]) == len(boundaries)
        for c, b in zip(by_name[child], boundaries):
            assert _contained(c, b)
    # The boundary span opens where the meter returned: after the read-back.
    for rb, b in zip(by_name["train.readback_wait"][-len(boundaries):],
                     boundaries):
        assert rb[2] + rb[3] <= b[2]


def _recording(calls, name, real):
    """``real`` wrapped to note ``(name, step)`` first; the step is the first
    positional argument or ``global_step``."""
    def wrapper(*args, **kwargs):
        calls.append((name, args[0] if args else kwargs["global_step"]))
        return real(*args, **kwargs)
    return wrapper


@pytest.mark.parametrize("unroll", [1, 2], ids=["per_step", "unrolled"])
def test_boundary_order_planes_then_health_then_history_then_callback(
        unroll, monkeypatch):
    """The order inside one log boundary, which each stage's reader stands
    on: the snapshot is emitted (``emit_metrics``) before the health
    monitor observes, the history sample (and its alert tick) comes after
    both, and the caller's ``on_metrics`` is last."""
    from autodist_tpu.telemetry import health, history
    runner, params, batch = _linear_session()
    calls = []
    monitor = health.HealthMonitor(health.HealthConfig(action="warn"))
    monkeypatch.setattr(telemetry, "emit_metrics", _recording(
        calls, "emit_metrics", telemetry.emit_metrics))
    monkeypatch.setattr(monitor, "observe", _recording(
        calls, "health", monitor.observe))
    monkeypatch.setattr(history, "maybe_sample", _recording(
        calls, "history", history.maybe_sample))
    telemetry.enable()
    train(runner, params, lambda i: batch, steps=9, log_every=2,
          unroll=unroll, prefetch_depth=0, health_monitor=monitor,
          on_metrics=_recording(calls, "on_metrics", lambda *a: None))
    steps = [step for name, step in calls if name == "on_metrics"]
    assert len(steps) >= 3
    order = ["emit_metrics", "health", "history", "on_metrics"]
    for k, step in enumerate(steps):
        assert calls[4 * k:4 * k + 4] == [(name, step) for name in order]
    # After the last boundary only the end-of-run flushes are left: the
    # monitor's tail period (a block that closed no period) and the forced
    # history sample.
    assert [name for name, _ in calls[4 * len(steps):]] in (
        ["history"], ["health", "history"])


class _FakeWireStats:
    def format_line(self):
        return "wire FAKE"


@pytest.mark.parametrize("prefetch_depth", [0, 2], ids=["inline", "prefetch"])
@pytest.mark.parametrize("unroll", [1, 2], ids=["per_step", "unrolled"])
def test_log_line_and_queue_depth_are_the_same_for_a_step_and_a_block(
        unroll, prefetch_depth, monkeypatch):
    """What only one of the two old loops did, both dispatch kinds do now:
    the log line ends with the runner's ``wire_stats`` when it has any, and
    ``train.dispatch_queue_depth`` is booked after every dispatch (the
    producer's fill; 0 without one)."""
    from autodist_tpu.utils import logging as adlog
    runner, params, batch = _linear_session()
    runner.wire_stats = _FakeWireStats
    lines = []
    monkeypatch.setattr(adlog, "info",
                        lambda msg, *args: lines.append(msg % args))
    telemetry.enable()
    seen = []
    train(runner, params, lambda i: batch, steps=9, log_every=2,
          unroll=unroll, prefetch_depth=prefetch_depth,
          on_metrics=lambda step, loss, rate: seen.append(step))
    period_lines = [l for l in lines if "examples/s" in l]
    assert len(period_lines) == len(seen) >= 3
    for line, step in zip(period_lines, seen):
        assert line.startswith(f"train: step {step} loss ")
        assert re.search(r"\| q \d+ rb \d+\.\d{3}s \| wire FAKE", line)
        if not prefetch_depth:
            assert "| q 0 rb" in line
    gauge = telemetry.registry().get("train.dispatch_queue_depth")
    assert gauge is not None
    assert gauge.value == 0 if not prefetch_depth \
        else 0 <= gauge.value <= prefetch_depth


def test_boundary_without_telemetry_records_nothing_and_pays_no_planes():
    runner, params, batch = _linear_session()
    seen = []
    train(runner, params, lambda i: batch, steps=5, log_every=2,
          prefetch_depth=0, on_metrics=lambda *a: seen.append(a[0]))
    assert seen and telemetry.snapshot_spans() == []


# ------------------------------------------------- set-up seconds, counters

def _counter(name):
    instrument = telemetry.registry().get(name)
    return 0 if instrument is None else instrument.value


def test_setup_seconds_are_booked_with_telemetry_off():
    assert not telemetry.enabled()
    names = ("setup.strategy_build_s", "setup.plan_build_s",
             "setup.state_place_s", "setup.state_place_calls")
    before = {n: _counter(n) for n in names}
    runner, params, batch = _linear_session()
    runner.init(params)
    runner.init(params)                        # every init counts
    after = {n: _counter(n) for n in names}
    assert after["setup.state_place_calls"] \
        == before["setup.state_place_calls"] + 2
    for n in names[:3]:
        assert after[n] > before[n], n
    assert telemetry.snapshot_spans() == []    # counters only: no span


def test_setup_work_is_a_span_of_the_counters_name_when_enabled():
    telemetry.enable()
    runner, params, batch = _linear_session()
    runner.init(params)
    names = {s[0] for s in telemetry.snapshot_spans()}
    assert {"setup.strategy_build_s", "setup.plan_build_s",
            "setup.state_place_s"} <= names


def test_jit_stage_listener_registers_once_and_books_with_telemetry_off():
    from jax._src import monitoring
    for _ in range(3):
        compile_cache.configure()
    assert monitoring.get_event_duration_listeners().count(
        compile_cache._on_jit_stage) == 1
    assert monitoring.get_event_time_span_listeners().count(
        compile_cache._on_trace_span) == 1
    names = ("jit.trace_s", "jit.lower_s", "jit.backend_s", "jit.programs")
    x = jnp.arange(7.0)                        # a program of its own
    before = {n: _counter(n) for n in names}
    assert not telemetry.enabled()
    jax.jit(lambda x: x * 3 + 1)(x).block_until_ready()
    after = {n: _counter(n) for n in names}
    assert after["jit.programs"] == before["jit.programs"] + 1
    for n in names[:3]:
        assert after[n] > before[n], n
    # Events of other kinds pass through without a counter of their own.
    compile_cache._on_jit_stage("/jax/compilation_cache/cache_hits", 1.0)
    compile_cache._on_trace_span("/jax/core/compile/other", 0.0, 1.0)
    assert {n: _counter(n) for n in names} == after


def test_configure_still_places_the_cache_on_an_accelerator(monkeypatch, tmp_path):
    """The branch the CPU suite never takes: with an accelerator backend and
    the directory given from outside, ``configure()`` sets no directory of
    its own, keeps every compile, and returns the directory in use."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, str(tmp_path))
    was_dir = jax.config.jax_compilation_cache_dir
    was_min = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        assert compile_cache.configure() == str(tmp_path)
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    finally:
        jax.config.update("jax_compilation_cache_dir", was_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          was_min)


def test_nested_traces_count_once():
    """A trace inside another reports its own span first; ``jit.trace_s``
    grows by the union, not by the sum."""
    event = compile_cache.TRACE_EVENT
    base = time.time() + 1e6                    # later than any real span
    before = _counter("jit.trace_s")
    compile_cache._on_trace_span(event, base + 1.0, base + 2.0)    # inner
    compile_cache._on_trace_span(event, base + 3.0, base + 3.5)    # inner
    assert _counter("jit.trace_s") == pytest.approx(before + 1.5)
    compile_cache._on_trace_span(event, base + 0.5, base + 4.0)    # encloses
    assert _counter("jit.trace_s") == pytest.approx(before + 3.5)
    compile_cache._on_trace_span(event, base + 5.0, base + 6.0)    # the next
    assert _counter("jit.trace_s") == pytest.approx(before + 4.5)
    # and on real programs: an outer jit that traces an inner one
    inner = jax.jit(lambda x: x * 2 + 1)
    t0 = time.perf_counter()
    before = _counter("jit.trace_s")
    jax.jit(lambda x: inner(x) + inner(x * 3))(jnp.arange(5.0))
    assert 0 < _counter("jit.trace_s") - before <= time.perf_counter() - t0


def test_dispatch_sits_in_a_step_annotation_only_when_enabled():
    from autodist_tpu.runner import _StepAnnotated
    from autodist_tpu.telemetry.spans import _NULL_SPAN
    runner, params, batch = _linear_session()
    state = runner.init(params)
    assert runner._dispatch_span("runner.run.dispatch", "step", None,
                                 batch) is _NULL_SPAN
    telemetry.enable()
    state, _ = runner.run(state, batch)
    state, _ = runner.run_many(state, [batch, batch, batch])
    assert runner._annotated_steps == 4        # 1 + a block of 3
    cm = runner._dispatch_span("runner.run.dispatch", "step", None, batch)
    assert isinstance(cm, _StepAnnotated)
