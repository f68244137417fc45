"""The AllReduce tuning knobs actually tune: group -> gradient bucketing,
spec -> hierarchical ICI/DCN reduce.

The reference wired ``group`` into ScopedAllocator fusion of CollectiveReduce
(``all_reduce_strategy.py:61-67``, ``runner.py:41-46``) and ``spec`` into the
collective implementation choice. TPU-native: in the explicit shard_map path,
params sharing a group id reduce as one concatenated buffer (fewer, larger
collectives — what ScopedAllocator bought), and spec=DCN lowers to a two-phase
reduce (intra-slice axis first, then cross-slice). Both are proven by HLO
inspection plus value-exactness against the unfused/flat lowering.
"""

import jax
import jax.numpy as jnp
import numpy as np

from autodist_tpu.model_spec import ModelSpec
from autodist_tpu.parallel import synchronization
from autodist_tpu.parallel.mesh import build_mesh
from autodist_tpu.parallel.plan import ShardingPlan
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.strategy import AllReduce

BATCH = 16
SPEC_8 = ResourceSpec("nodes: [{address: localhost, tpus: 8, chief: true}]")
SPEC_HIER = ResourceSpec(resource_info={
    "nodes": [{"address": "localhost", "tpus": 8, "chief": True}],
    "mesh": {"data": 2, "reduce": 4}})


def _params():
    rng = np.random.RandomState(0)
    return {f"w{i}": jnp.asarray(rng.randn(8, 4), jnp.float32) for i in range(4)}


def _batch(seed=1):
    rng = np.random.RandomState(seed)
    return {"x": rng.randn(BATCH, 8).astype(np.float32),
            "y": rng.randn(BATCH, 4).astype(np.float32)}


def _loss(p, b):
    # Per-param scale keeps the four gradients distinct (identical grads would be
    # CSE'd into one collective, confounding the counts).
    out = sum((i + 1.0) * (b["x"] @ p[k]) for i, k in enumerate(sorted(p)))
    return jnp.mean((b["y"] - out) ** 2)


def _grads_and_lowered(builder, resource_spec=SPEC_8):
    params, batch = _params(), _batch()
    model = ModelSpec.from_loss_fn(_loss, params, batch)
    strategy = builder.build(model, resource_spec)
    plan = ShardingPlan.from_strategy(strategy, model)
    mesh = build_mesh(axes=dict(plan.mesh_axes))
    grad_fn = synchronization.make_grad_fn(plan, model, mesh, _loss)
    ef = synchronization.init_ef_state(plan, params, mesh=mesh)
    # Pre-optimization lowering: what OUR sync emits (the compiled module also
    # reflects XLA's own combiner, which would mask the knob under test).
    text = jax.jit(grad_fn).lower(params, batch, ef).as_text()
    with mesh:
        grads, *_ = jax.jit(grad_fn)(params, batch, ef)
    return grads, text


def _count_all_reduce(text):
    return sum("stablehlo.all_reduce" in l for l in text.splitlines())


def test_group_bucketing_fuses_collectives():
    """chunk_size=4 puts all four 8x4 grads in one group: ONE concatenated
    collective (+1 for the loss) instead of four per-leaf ones."""
    _, flat = _grads_and_lowered(AllReduce(chunk_size=1, compressor="HorovodCompressor"))
    _, fused = _grads_and_lowered(AllReduce(chunk_size=4, compressor="HorovodCompressor"))
    assert _count_all_reduce(flat) == 5    # 4 grads + loss
    assert _count_all_reduce(fused) == 2   # 1 bucket + loss
    assert "tensor<128xbf16>" in fused     # 4 * (8*4) elements, bf16 on the wire


def test_bucketing_is_value_exact():
    """The bf16 cast is elementwise, so bucketed and per-leaf lowerings produce
    identical gradients."""
    g_flat, _ = _grads_and_lowered(AllReduce(chunk_size=1, compressor="HorovodCompressor"))
    g_fused, _ = _grads_and_lowered(AllReduce(chunk_size=4, compressor="HorovodCompressor"))
    for k in g_flat:
        np.testing.assert_array_equal(np.asarray(g_flat[k]), np.asarray(g_fused[k]))


def test_bucketing_with_error_feedback_value_exact():
    g_flat, _ = _grads_and_lowered(AllReduce(chunk_size=1, compressor="HorovodCompressorEF"))
    g_fused, text = _grads_and_lowered(AllReduce(chunk_size=4, compressor="HorovodCompressorEF"))
    assert _count_all_reduce(text) == 2
    for k in g_flat:
        np.testing.assert_array_equal(np.asarray(g_flat[k]), np.asarray(g_fused[k]))


def test_dcn_spec_lowers_to_two_phase_reduce():
    """spec=DCN on a {data:2, reduce:4} mesh: the bucketed gradient reduce becomes
    two all-reduce phases (intra-slice then cross-slice); AUTO stays single-phase.
    Results identical."""
    g_auto, auto = _grads_and_lowered(
        AllReduce(chunk_size=4, compressor="HorovodCompressor"), SPEC_HIER)
    g_dcn, dcn = _grads_and_lowered(
        AllReduce(chunk_size=4, compressor="HorovodCompressor",
                  all_reduce_spec="DCN"), SPEC_HIER)

    assert _count_all_reduce(auto) == 2   # 1 joint bucket reduce + loss
    assert _count_all_reduce(dcn) == 3    # 2 hierarchical phases + loss
    for k in g_auto:
        # Each hierarchical phase rounds to bf16 on the wire, so the two
        # schedules agree only to bf16 precision (~3 decimal digits).
        np.testing.assert_allclose(np.asarray(g_auto[k]), np.asarray(g_dcn[k]),
                                   rtol=2e-2, atol=2e-2)


def test_no_compression_keeps_implicit_path():
    """NONE-only strategies stay on the implicit SPMD lowering (no shard_map):
    XLA's all-reduce combiner performs the fusion the group ids request, so the
    knob is honored without forcing a manual data path."""
    params, batch = _params(), _batch()
    model = ModelSpec.from_loss_fn(_loss, params, batch)
    strategy = AllReduce(chunk_size=4).build(model, SPEC_8)
    plan = ShardingPlan.from_strategy(strategy, model)
    mesh = build_mesh(axes=dict(plan.mesh_axes))
    grad_fn = synchronization.make_grad_fn(plan, model, mesh, _loss)
    hlo = jax.jit(grad_fn).lower(
        params, batch, synchronization.init_ef_state(plan, params, mesh=mesh)
    ).as_text()
    assert "shard_map" not in hlo
