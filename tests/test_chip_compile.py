"""Ask the chip's compiler, without the chip.

Interpret mode runs the Pallas kernels' arithmetic but none of Mosaic's
checks: tiling alignment, the scoped-VMEM limit (16 MiB unless the call asks
for more), lowering of each op.
libtpu compiles for a TPU that is described and not attached, so these cases
compile the main path's kernels at real widths for one chip of a ``v5e:2x2``
and assert that each lowered to a Mosaic custom call. Nothing executes: a
pass here is not a chip run. Skipped where the topology cannot be described
(no libtpu, or another process holds its lock).
"""

import importlib
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import topologies
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from autodist_tpu.ops import fused_xent as fx

# ``autodist_tpu.ops.flash_attention`` the attribute is the function.
fa = importlib.import_module("autodist_tpu.ops.flash_attention")


@pytest.fixture(scope="module")
def topology():
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe is a skip
        pytest.skip(f"cannot describe a v5e topology here: {e}")


@pytest.fixture(scope="module")
def chip(topology):
    return SingleDeviceSharding(topology.devices[0])


@pytest.fixture(autouse=True)
def compile_not_interpret(monkeypatch):
    """The backend is the CPU, so the kernels would pick interpret mode:
    steer them to compile, here and not through an option of the program.
    A compile for a described chip can be written to the persistent cache
    but not read back without the chip, so the cache is off around it."""
    # the kernels of the other ops modules look it up in ``fa`` at call time
    monkeypatch.setattr(fa, "_use_interpret", lambda: False)
    monkeypatch.setattr(fx, "_use_interpret", lambda: False)
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _compiled_text(fn, chip, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            for shape, dtype in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("batch,length,heads,depth", [
    (4, 2048, 8, 64), (4, 300, 8, 64), (4, 2048, 8, 128),
    (8, 1024, 16, 64),      # gpt2m-pretrain-1k / gpt2m-dp4-sync, a chip and call
    (2, 1100, 8, 64),       # K/V resident and padded to whole 512-key tiles
    (1, 16384, 8, 64),      # past the resident limit: K/V streamed in blocks
    (4, 4096, 16, 128),     # olmoe-pretrain-4k: K/V of a head exactly the resident 1 MB
    (1, 32768, 2, 128),     # 16 MiB of float32 dQ a head: the split backward
], ids=["L2048-D64", "ragged-L300", "D128", "cell-8x1024x16x64", "ragged-L1100",
        "streamed-L16384", "olmoe-4x4096x16x128", "split-L32768-D128"])
def test_flash_attention_fwd_bwd_compiles(chip, batch, length, heads, depth):
    qkv = ((batch, length, heads, depth), jnp.bfloat16)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 2)), chip,
                          qkv, qkv, qkv)
    assert "tpu_custom_call" in text
    # one pass while float32 dQ of a (batch, head) stays in VMEM, two kernels past it
    one_pass = -(-length // 512) * 512 * depth * 4 <= fa._RESIDENT_DQ_BYTES
    assert ("flash_bwd_dq" in text) != one_pass


@pytest.mark.parametrize("window", [2048, None], ids=["window-2048", "full"])
def test_flash_window_and_grouped_heads_compile_at_the_trinity_cell_shapes(
        chip, window):
    """trinity-pretrain-8k's calls: 1 x 8,192 x 32 query heads over 4 KV
    heads of 128, a sliding layer (window 2,048) and the full one; K/V of a
    head is 2 MB, so the forward streams it in 2,048-row blocks; float32 dQ of
    a head is 4 MB, the most the backward takes in one pass under 48 MiB of
    scoped VMEM."""
    q = ((1, 8192, 32, 128), jnp.bfloat16)
    kv = ((1, 8192, 4, 128), jnp.bfloat16)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True,
                                  window=window).astype(jnp.float32).sum()

    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 2)), chip,
                          q, kv, kv)
    assert "flash_fwd" in text and "flash_bwd_dkv" in text
    assert 8192 * 128 * 4 == fa._SMALL_DQ_BYTES and "flash_bwd_dq" not in text


def test_flash_carry_variant_compiles(chip):
    """The ring-attention local step: (acc, m, l) carry in and out."""
    b, length, h, d = 1, 4096, 8, 64
    qkv = ((b, length, h, d), jnp.bfloat16)

    def step(q, k, v, acc, m, l):
        return fa.flash_attention_with_carry(q, k, v, (acc, m, l), causal=True,
                                             k_offset=length)

    text = _compiled_text(step, chip, qkv, qkv, qkv,
                          ((b, h, length, d), jnp.float32),
                          ((b, h, length), jnp.float32),
                          ((b, h, length), jnp.float32))
    assert "tpu_custom_call" in text


def test_flash_ring_backward_step_compiles(chip):
    """One step of ring attention's backward as ``_ring_flash_bwd`` runs it:
    traced offsets (the classes decided from SMEM scalars at run time),
    float32 dQ / dK / dV, 512-row blocks asked for."""
    b, length, h, d = 1, 4096, 8, 64
    qkv = ((b, length, h, d), jnp.bfloat16)

    def step(q, k, v, o, lse, g, q_offset, k_offset):
        qf, dof, dd, bq, n_q = fa.prepare_backward_q_side(q, o, g, 512)
        return fa._flash_backward_kv(
            qf, dof, lse, dd, k, v, True, bq, n_q, 512, False, q.shape,
            q_offset=q_offset, k_offset=k_offset, out_dtype=jnp.float32)

    text = _compiled_text(step, chip, qkv, qkv, qkv, qkv,
                          ((b * h, length // 512, 512), jnp.float32), qkv,
                          ((), jnp.int32), ((), jnp.int32))
    assert "flash_bwd_dkv" in text and "flash_bwd_dq" not in text


# Row count 2,048: the tiles, not the rows, decide what the compiler accepts,
# and N=98,304 (the flagship) takes ten times as long for the same verdict.
_XENT_ROWS = 2048


@pytest.mark.parametrize("d,vocab,layout,rows_dtype,table_dtype,tiles,one_pass", [
    # (bn, bv) of forward, dh, dw: what ``_fit_blocks`` picks for each, under
    # Mosaic's default 16 MiB where tiles this large fit it and under the
    # 48 MiB the call asks for where they do not; and of the one-pass
    # backward, which runs in their place wherever the rule gives it (None:
    # the two kernels run)
    (512, 32_000, "dv", jnp.bfloat16, jnp.float32,   # the flagship head
     ((1024, 1024), (1024, 512), (1024, 512)), (1024, 512)),
    (1024, 32_000, "vd", jnp.bfloat16, jnp.float32,
     ((512, 1024), (512, 512), (512, 512)), (1024, 512)),
    # The two that libtpu 0.0.34 refused at (512, 1024) tiles (18.39M and
    # 16.73M scoped against 16M) until _fit_blocks counted the in-kernel
    # temporaries. A bfloat16 table: dw cannot accumulate in it.
    (1024, 32_000, "dv", jnp.bfloat16, jnp.bfloat16,
     ((512, 1024), (512, 512), (512, 512)), None),
    (1024, 50_257, "vd", jnp.bfloat16, jnp.bfloat16,
     ((512, 1024), (512, 512), (512, 512)), None),
    (2048, 50_304, "dv", jnp.bfloat16, jnp.float32,  # olmoe-pretrain-4k's untied head
     ((1024, 1024), (512, 512), (1024, 512)), (1024, 512)),
    # float32 rows at d 2,048: the forward alone was refused under 16 MiB
    (2048, 50_304, "dv", jnp.float32, jnp.float32,
     ((1024, 1024), (512, 512), (512, 512)), (1024, 128)),
    (2048, 25_024, "dv", jnp.bfloat16, jnp.float32,  # trinity-pretrain-8k's sliced head
     ((1024, 1024), (512, 512), (1024, 512)), (1024, 512)),
    # nemotron-pretrain-8k's sliced head: d 2,688 is past the 512-2,048 the
    # temporaries were fitted for, and the fit holds (PR 35)
    (2688, 16_384, "dv", jnp.bfloat16, jnp.float32,
     ((512, 1024), (512, 512), (512, 512)), (1024, 256)),
], ids=["flagship-d512-dv-f32", "d1024-vd-f32", "d1024-dv-bf16",
        "d1024-vd-bf16-V50257", "olmoe-d2048-dv-f32", "d2048-f32-rows",
        "trinity-d2048-dv-f32", "nemotron-d2688-dv-f32"])
def test_fused_xent_fwd_bwd_compiles(chip, d, vocab, layout, rows_dtype,
                                     table_dtype, tiles, one_pass):
    table = (vocab, d) if layout == "vd" else (d, vocab)
    sizes = (jnp.dtype(rows_dtype).itemsize, jnp.dtype(table_dtype).itemsize)
    assert tuple(fx._fit_blocks(kernel, _XENT_ROWS, d, vocab, *sizes)
                 for kernel in ("fwd", "dh", "dw")) == tiles
    assert fx._fit_blocks("bwd", _XENT_ROWS, d, vocab, *sizes) == one_pass

    def loss(h, w, targets):
        return fx.fused_softmax_xent(h, w, targets, w_layout=layout).mean()

    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1)), chip,
                          ((_XENT_ROWS, d), rows_dtype), (table, table_dtype),
                          ((_XENT_ROWS,), jnp.int32))
    assert "tpu_custom_call" in text
    # the one pass runs under dw's name; dh's is the two-kernel path's alone
    assert "xent_fwd" in text and "xent_bwd_dw" in text
    assert ("xent_bwd_dh" in text) == (one_pass is None)


@pytest.mark.parametrize("rows,d,vocab,layout", [
    (16_384, 2048, 50_304, "dv"),        # olmoe-pretrain-4k
    (8_192, 1024, 50_257, "vd"),         # gpt2m-pretrain-1k, gpt2m-dp4-sync
    (8_192, 2048, 25_024, "dv"),         # trinity-pretrain-8k
    (1_000, 1024, 50_257, "vd"),         # one ragged row block: the masked h tile
], ids=["olmoe", "gpt2", "trinity", "ragged-rows"])
def test_one_pass_backward_fits_the_vmem_its_model_counts(chip, rows, d, vocab,
                                                          layout):
    """``_vmem_need("bwd", ...)`` is never under the compiler's count: at the
    cells' calls the one pass compiles under a scoped limit of exactly what
    the model says its tiles take (bfloat16 rows, a float32 table)."""
    from jax.experimental.pallas import tpu as pltpu

    w_vd = layout == "vd"
    bn, bv = fx._fit_blocks("bwd", rows, d, vocab, 2, 4)
    need = int(fx._vmem_need("bwd", d, bn, bv, 2, 4))
    assert need <= fx._BWD_VMEM_BUDGET

    def backward(h, w, b, lse, g):
        return fx._backward_one_pass(
            h, w, b, lse, g, bn, bv,
            pltpu.CompilerParams(vmem_limit_bytes=need), False, w_vd)[:3]

    text = _compiled_text(
        backward, chip, ((rows, d), jnp.bfloat16),
        ((vocab, d) if w_vd else (d, vocab), jnp.float32),
        ((vocab,), jnp.float32), ((rows,), jnp.float32), ((rows,), jnp.float32))
    assert "xent_bwd_dw" in text and "xent_bwd_dh" not in text


@pytest.mark.parametrize("k,n", [(2048, 1024), (1024, 2048)],
                         ids=["gate-up-2048x1024", "down-1024x2048"])
def test_grouped_matmul_fwd_bwd_compiles_at_the_olmoe_cell_shapes(chip, k, n):
    """131,072 routed rows (16,384 tokens x top-8) in 64 groups: the forward,
    dX and dW kernels, each a Mosaic call under its own name."""
    from autodist_tpu.ops.grouped_matmul import gmm

    def loss(x, w, group_sizes):
        return gmm(x, w, group_sizes).astype(jnp.float32).sum()

    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1)), chip,
                          ((131_072, k), jnp.bfloat16),
                          ((64, k, n), jnp.float32), ((64,), jnp.int32))
    assert "tpu_custom_call" in text
    for name in ("moe_gmm_fwd", "moe_gmm_bwd_dx", "moe_gmm_bwd_dw"):
        assert name in text


@pytest.mark.parametrize("k,n", [(2048, 1024), (1024, 2048)],
                         ids=["gate-up-2048x1024", "down-1024x2048"])
def test_grouped_matmul_fwd_bwd_compiles_at_the_trinity_cell_shapes(chip, k, n):
    """The 8,192-row bound of one chip's share (8 of 128 experts held, 4,096
    rows on average, the tail past the last group empty) in 8 groups."""
    from autodist_tpu.ops.grouped_matmul import gmm

    def loss(x, w, group_sizes):
        return gmm(x, w, group_sizes).astype(jnp.float32).sum()

    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1)), chip,
                          ((8192, k), jnp.bfloat16),
                          ((8, k, n), jnp.float32), ((8,), jnp.int32))
    for name in ("moe_gmm_fwd", "moe_gmm_bwd_dx", "moe_gmm_bwd_dw"):
        assert name in text


@pytest.mark.parametrize("k,n", [(2048, 1536), (1536, 2048)],
                         ids=["gate-up-2048x1536", "down-1536x2048"])
def test_grouped_matmul_fwd_bwd_compiles_at_the_lfm2_cell_shapes(chip, k, n):
    """A 16,384-row pass of one chip's share (8 of 64 experts held, 8,192 rows
    on average) in 8 groups of experts 1,536 wide: ``_col_tile(1536)`` is 512
    where the other cells' 1,024 takes 1,024-column blocks."""
    from autodist_tpu.ops import grouped_matmul

    assert grouped_matmul._col_tile(1536) == 512

    def loss(x, w, group_sizes):
        return grouped_matmul.gmm(x, w, group_sizes).astype(jnp.float32).sum()

    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1)), chip,
                          ((16_384, k), jnp.bfloat16),
                          ((8, k, n), jnp.float32), ((8,), jnp.int32))
    for name in ("moe_gmm_fwd", "moe_gmm_bwd_dx", "moe_gmm_bwd_dw"):
        assert name in text


@pytest.mark.parametrize("k,n", [(2688, 1856), (1856, 2688)],
                         ids=["up-2688x1856", "down-1856x2688"])
def test_grouped_matmul_fwd_bwd_compiles_at_the_nemotron_cell_shapes(chip, k, n):
    """A 6,144-row pass of one chip's share (8 of 128 experts held, 3,072 rows
    on average) in 8 groups of ``relu2`` experts 1,856 wide under a hidden
    size of 2,688: a width of 21 x 128 in 896-column blocks, one of 14.5 x 128
    as one block and as a whole contraction."""
    from autodist_tpu.ops import grouped_matmul

    assert (grouped_matmul._col_tile(2688), grouped_matmul._col_tile(1856)) \
        == (896, 1856)

    def loss(x, w, group_sizes):
        return grouped_matmul.gmm(x, w, group_sizes).astype(jnp.float32).sum()

    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1)), chip,
                          ((6144, k), jnp.bfloat16),
                          ((8, k, n), jnp.float32), ((8,), jnp.int32))
    for name in ("moe_gmm_fwd", "moe_gmm_bwd_dx", "moe_gmm_bwd_dw"):
        assert name in text


@pytest.mark.parametrize("length,chunks,rows", [
    (8192, 64, False), (1000, 8, False), (8192, 64, True), (1000, 8, True)],
    ids=["cell-1x8192", "ragged-L1000", "cell-rows", "ragged-rows"])
def test_ssd_scan_fwd_bwd_compiles_at_the_nemotron_cell_shape(chip, length,
                                                              chunks, rows):
    """nemotron-pretrain-8k's call: 64 heads of 64 in 8 groups, state 128,
    chunks of 128, bfloat16 ``x``, ``B``, ``C`` and float32 ``dt``: the
    forward and the backward kernel, each a Mosaic call under its own name,
    through the operator's custom VJP; a length the chunk does not divide is
    padded. ``rows``: as the cell hands it since PR 49, ``[x | B | C]`` one
    array of 6,144 columns as the convolution wrote it; around the two calls
    nothing of its size or of ``y``'s is then laid out anew."""
    from autodist_tpu.ops.ssd_scan import ssd_scan

    small = (((1, length, 64), jnp.float32), ((64,), jnp.float32))
    if rows:
        def loss(xbc, dt, a, d):
            return ssd_scan(xbc, dt, a, None, None, d, impl="pallas",
                            groups=(8, 128)).astype(jnp.float32).sum()
        shapes = (((1, length, 6144), jnp.bfloat16), *small, ((64,), jnp.float32))
    else:
        def loss(x, dt, a, b, c, d):
            return ssd_scan(x, dt, a, b, c, d, impl="pallas").astype(jnp.float32).sum()
        narrow = ((1, length, 8, 128), jnp.bfloat16)
        shapes = (((1, length, 64, 64), jnp.bfloat16), *small, narrow, narrow,
                  ((64,), jnp.float32))
    text = _compiled_text(jax.value_and_grad(
        loss, argnums=tuple(range(len(shapes)))), chip, *shapes)
    assert "tpu_custom_call" in text
    assert "ssd_fwd" in text and "ssd_bwd" in text
    assert f"f32[1,{chunks},8,512,128]" in text      # one state a chunk and head
    if rows:
        padded = chunks * 128
        assert not _relayouts(text, {n * w for n in (length, padded)
                                     for w in (4096, 6144)})


def test_flash_sixteen_query_heads_a_kv_head_compile_at_the_nemotron_cell_shape(chip):
    """nemotron-pretrain-8k's call: 1 x 8,192 x 32 query heads over 2 KV
    heads of 128, causal, no window: Trinity's full layer's schedule at 16
    query heads a KV head where Trinity has 8 and LFM2 4. No new code; this
    guards it."""
    q = ((1, 8192, 32, 128), jnp.bfloat16)
    kv = ((1, 8192, 2, 128), jnp.bfloat16)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 2)), chip,
                          q, kv, kv)
    assert "flash_fwd" in text and "flash_bwd_dkv" in text


@pytest.mark.parametrize("shared", [True, False], ids=["shared-key", "assembled"])
def test_flash_at_two_widths_compiles_at_the_kanana_cell_shape(chip, shared):
    """kanana-pretrain-16k's call: 1 x 16,384 x 32 heads, keys 192 wide (128
    a head + the 64 rotary columns all heads share, as a second operand or
    assembled by the caller), values 128: the forward with K/V of a head
    resident (4 MiB of keys 128 wide and the 2 MiB all heads share, under 32
    MiB of scoped VMEM; assembled, 192 wide, they stream in 8,192-row
    blocks), 1.5 lane tiles of key width, and the backward in one pass at 12
    MiB of float32 dQ a head, which takes more scoped VMEM than the 48 MiB
    the older calls ask for."""
    b, length, h = 1, 16384, 32
    shapes = [((b, length, h, 192), jnp.bfloat16),
              ((b, length, h, 128 if shared else 192), jnp.bfloat16),
              ((b, length, h, 128), jnp.bfloat16)]
    if shared:
        shapes.append(((b, length, 64), jnp.bfloat16))

    def loss(q, k, v, k_shared=None):
        return fa.flash_attention(q, k, v, causal=True,
                                  k_shared=k_shared).astype(jnp.float32).sum()

    text = _compiled_text(
        jax.value_and_grad(loss, argnums=tuple(range(len(shapes)))), chip, *shapes)
    assert 16384 * 192 * 4 == fa._RESIDENT_DQ_BYTES
    assert 16384 * 128 * 2 == fa._RESIDENT_KV_BYTES     # K of a head: resident
    assert fa._forward_blocks(length, length, 128 if shared else 192, 2, None,
                              None)[1] == (length if shared else 8192)
    assert "flash_fwd" in text and "flash_bwd_dkv" in text
    assert "flash_bwd_dq" not in text


@pytest.mark.parametrize("cell,b,length,h,h_kv,d,window,rows,operand", [
    ("kanana", 1, 16384, 32, 32, 192, None, "kv", "bf16[1,16384,8192]"),
    ("nemotron", 1, 8192, 32, 2, 128, None, "qkv", "bf16[1,8192,4096]"),
    ("olmoe", 4, 4096, 16, 16, 128, None, "v", "bf16[4,4096,2048]"),
    ("trinity-sliding", 1, 8192, 32, 4, 128, 2048, "v", "bf16[1,8192,512]"),
])
def test_flash_on_rows_compiles_at_the_cells_shapes(chip, cell, b, length, h,
                                                    h_kv, d, window, rows,
                                                    operand):
    """Kanana's, Nemotron's and Trinity's calls with the operands their
    models hand as a projection's rows, ``[B, L, heads * D]`` (and OLMoE's
    shape with v so, which its model does not: it measured nothing there,
    PERF.md §6 "PR 41"): the kernels take those rows
    themselves (the custom calls' operand is the array as handed, a column
    block a head; Kanana's is ``kv_up``'s whole output, keys at block ``2 *
    head`` and values at ``2 * head + 1``, and d(kv) comes back as one
    array), forward and backward in one pass."""
    def struct(name, n, width):
        return ((b, length, n * width) if name in rows else (b, length, n, width),
                jnp.bfloat16)

    if cell == "kanana":
        shapes = [struct("q", h, d), struct("kv", h_kv, 256),
                  ((b, length, 64), jnp.bfloat16)]

        def loss(q, kv, ks):
            return fa.flash_attention(q, kv, None, k_shared=ks, heads=(h, h_kv)
                                      ).astype(jnp.float32).sum()
    else:
        shapes = [struct("q", h, d), struct("k", h_kv, d), struct("v", h_kv, d)]

        def loss(q, k, v):
            return fa.flash_attention(q, k, v, window=window, heads=(h, h_kv)
                                      ).astype(jnp.float32).sum()

    text = _compiled_text(
        jax.value_and_grad(loss, argnums=tuple(range(len(shapes)))), chip, *shapes)
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 2 and "flash_bwd_dq" not in text
    assert all(operand in line.split("custom-call(")[1] for line in calls)


def _kernel_launches(text: str, kernel: str) -> int:
    return sum("custom-call(" in line and kernel in line
               for line in text.splitlines())


@pytest.mark.parametrize("kind,exact,kept,bare", [
    ("M", True, dict(ssd_fwd=1, conv_silu_fwd=2, ssd_bwd=1, conv_silu_bwd=1,
                     gated_norm_fwd=2, gated_norm_bwd=1), dict(ssd_fwd=2)),
    ("*", False, dict(flash_fwd=1, flash_bwd_dkv=1), dict(flash_fwd=2)),
], ids=["mamba-exact", "attention"])
def test_a_checkpointed_nemotron_layer_launches_each_kept_forward_kernel_once(
        chip, kind, exact, kept, bare):
    """One layer of nemotron-pretrain-8k at its widths and 8,192 positions
    under ``jax.checkpoint`` with the model's policy (``KEPT``): what the
    kernels' forward rules hand their backward is kept by name, so the
    compiled gradient launches those forward kernels once (the convolution's
    and the gated norm's, whose outputs are not on the list, twice); under a
    bare ``jax.checkpoint`` (the parent's) it launches each twice."""
    from autodist_tpu.models import common, nemotron_h
    cfg = nemotron_h.NemotronHConfig(attention_impl="flash", ssm_impl="pallas")
    block = nemotron_h.NemotronHBlock(cfg, kind, exact)
    x = ((1, 8192, cfg.d_model), jnp.float32)
    params = jax.eval_shape(
        lambda: block.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, cfg.d_model)))
    )["params"]
    params = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=chip), params)

    def layer(params, x):
        y, term = block.apply({"params": params}, x)
        return jnp.sum(jnp.square(y)) + term

    def launches(policy):
        fn = jax.value_and_grad(jax.checkpoint(layer, policy=policy), argnums=(0, 1))
        text = jax.jit(fn).lower(
            params, jax.ShapeDtypeStruct(*x, sharding=chip)).compile().as_text()
        return {k: _kernel_launches(text, k) for k in kept}

    assert launches(common.keeping(nemotron_h.KEPT)) == kept
    assert launches(None) == dict(kept, **bare)


@pytest.mark.parametrize("exact", [False, True], ids=["bfloat16", "exact"])
def test_a_checkpointed_mamba2_layer_lays_no_wide_array_out_anew(chip, exact):
    """One Mamba-2 layer of nemotron-pretrain-8k under its checkpoint,
    compiled for the described v5e: between ``in_proj`` and ``out_proj`` the
    convolution, the scan and the gated norm read and write ``[B, L,
    columns]`` rows where their neighbours hold them, so the text holds no
    ``copy``, ``transpose`` or non-bitcast ``reshape`` of ``8192 x 4096`` (z,
    x, y and the norm's rows) or ``8192 x 6144`` (xBC) elements, and no slice
    that cuts them out of ``[z | xBC | dt]`` (1,342 + 503 + 134 MB of such
    passes a layer on the parent: PERF.md section 6, "PR 49")."""
    from autodist_tpu.models import common, nemotron_h
    cfg = nemotron_h.NemotronHConfig(attention_impl="flash", ssm_impl="pallas")
    mixer = nemotron_h.Mamba2(cfg, exact)
    dtype = jnp.float32 if exact else jnp.bfloat16
    params = jax.eval_shape(lambda: mixer.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8, cfg.d_model), dtype)))
    params = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=chip), params)
    layer = jax.checkpoint(
        lambda params, h: mixer.apply(params, h).astype(jnp.float32).sum(),
        policy=common.keeping(nemotron_h.KEPT))
    text = jax.jit(jax.grad(layer, argnums=(0, 1))).lower(
        params, jax.ShapeDtypeStruct((1, 8192, cfg.d_model), dtype, sharding=chip)
    ).compile().as_text()
    assert not _relayouts(text, {8192 * 4096, 8192 * 6144})
    assert not re.search(r"\[1,8192,(4096|6144)\]\S* slice\(", text)
    assert {k: _kernel_launches(text, k) for k in
            ("ssd_fwd", "ssd_bwd", "conv_silu_bwd", "gated_norm_bwd")} == dict(
        ssd_fwd=1, ssd_bwd=1, conv_silu_bwd=1, gated_norm_bwd=1)


@pytest.mark.slow    # a whole step: about 100 s
def test_the_nemotron_cells_step_fits_its_ceiling_with_the_kept_values(topology):
    """``benchmark/rehearse.py nemotron-pretrain-8k`` as a test: the cell's
    whole step compiles for the described chip and needs at most 12.4 GiB
    (10.238 with nothing kept; 2.48 GiB of parameters handed to ``train()``
    sit beside it on the chip's 15.75)."""
    from benchmark import harness, rehearse
    facts = rehearse.compile_cell(harness.load_cell("nemotron-pretrain-8k"),
                                  topology.devices)
    assert facts["tpu_custom_call"] and 10.3 < facts["step_gib"] <= 12.4


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("tokens,rows,d", [(8192, 8192, 2048),
                                           (16_384, 16_384, 2048),
                                           (8192, 6144, 2688)],
                         ids=["trinity-8192x8192", "lfm2-16384x16384",
                              "nemotron-8192x6144-d2688"])
def test_row_kernels_compile_at_the_share_cells_shapes(chip, tokens, rows, d,
                                                       dtype):
    """One pass of one chip's share at d 2,048 (the rows of the two cases
    above): the gather by token (one DMA a held row from a source left in
    HBM), and the combine with weights into float32 and without them into the
    rows' dtype (the dispatch's transpose), each a Mosaic call under its own
    name; float32 rows (8 KB slabs) as well as bfloat16 ones. At d 2,688 a
    row is 21 slab rows of 128 lanes, padded to 24: a DMA takes whole tiles
    of 8 (Mosaic refused the 21: PR 35)."""
    from autodist_tpu.ops import moe_rows

    def both(src, out, weight, token, count):
        plan = moe_rows.combine_plan(token, count, tokens)
        return (moe_rows.moe_rows_gather(src, token, count),
                moe_rows.moe_rows_combine(out, weight, token, count, tokens, plan),
                moe_rows.moe_rows_combine(out, None, token, count, tokens, plan,
                                          dtype=dtype))

    text = _compiled_text(both, chip, ((tokens, d), dtype),
                          ((rows, d), dtype), ((rows,), jnp.float32),
                          ((rows,), jnp.int32), ((), jnp.int32))
    assert "tpu_custom_call" in text
    assert "moe_rows_gather" in text and "moe_rows_combine" in text


def _indexed_scalar_moves(text: str, at_least: int):
    """The ``gather`` and ``scatter`` instructions of a compiled text, inside
    its fusions too, that move ``at_least`` scalars or more one by one (every
    slice of one element): a gather by its result's elements, a scatter by
    its updates'."""
    elements = {m[1]: int(np.prod([int(d) for d in m[2].split(",") if d] or [1]))
                for m in re.finditer(r"%([\w.-]+) = \w+\[([0-9,]*)\]", text)}
    found = []
    for m in re.finditer(r"%([\w.-]+) = \S+ (gather|scatter)\(([^)]*)\)(.*)", text):
        name, op, operands, rest = m.groups()
        if op == "gather":
            one_by_one = set(re.search(r"slice_sizes=\{([0-9,]*)\}", rest)[1]
                             .split(",")) == {"1"}
            moved = elements[name]
        else:
            one_by_one = "update_window_dims={}" in rest
            moved = elements[operands.split(",")[-1].strip().lstrip("%")]
        if one_by_one and moved >= at_least:
            found.append(m[0][:160])
    return found


@pytest.mark.parametrize("form,moves", [("now", 0), ("before PR 51", 3)])
def test_a_shares_routing_at_the_lfm2_cell_shape_moves_no_scalar_by_index(
        chip, form, moves):
    """One expert layer's routing of lfm2-pretrain-8k (16,384 tokens, the top
    4 of 64 sigmoid scores under a bias, 8 experts held), forward and
    gradient, in scope ``moe.route``: the optimized text for the described
    v5e holds no gather or scatter of the 65,536 routed choices one scalar at
    a time. The forms it had before PR 51 (``take_along_axis`` and its
    transpose, ``flat[perm]``: 6.3 ms of the cell's step, PERF.md section 6)
    hold three, which shows the search finds them."""
    from autodist_tpu.models import moe
    from tests.test_moe_route import route_before
    tokens, width, top_k = 16_384, 64, 4
    route = moe.sigmoid_topk_route if form == "now" else route_before

    def loss(scores, bias, ct):
        with jax.named_scope("moe.route"):
            r = route(scores, top_k, bias, n_held=8, route_eps=1e-6)
        # what ``routed_experts`` reads of a share's routing
        return jnp.sum(r.weights * ct), (r.indices, r.group_sizes, r.perm)

    text = _compiled_text(jax.value_and_grad(loss, has_aux=True), chip,
                          ((tokens, width), jnp.float32), ((width,), jnp.float32),
                          ((tokens, top_k), jnp.float32))
    assert "moe.route" in text
    found = _indexed_scalar_moves(text, tokens * top_k)
    assert len(found) == moves, found


def test_flash_grouped_heads_of_64_compile_at_the_lfm2_cell_shape(chip):
    """lfm2-pretrain-8k's call: 2 x 8,192 x 32 query heads over 8 KV heads of
    64, causal, no window. K of a head is 1 MiB, the most the forward kept
    resident until PR 39 (4 MiB since: kanana's call, guarded above) and the
    last whose blocks fit the compiler's default scoped VMEM, and float32 dQ
    of a head 2 MiB of what the one-pass backward takes."""
    q = ((2, 8192, 32, 64), jnp.bfloat16)
    kv = ((2, 8192, 8, 64), jnp.bfloat16)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 2)), chip,
                          q, kv, kv)
    assert 8192 * 64 * 2 <= fa._RESIDENT_KV_BYTES
    assert fa._forward_vmem_limit(8192 * (64 + 64) * 2) is None
    assert 8192 * 64 * 4 <= fa._RESIDENT_DQ_BYTES
    assert "flash_fwd" in text and "flash_bwd_dkv" in text
    assert "flash_bwd_dq" not in text


@pytest.mark.parametrize("batch,length,d,taps", [
    (2, 8192, 2048, 3),         # lfm2-pretrain-8k: bcu [2, 8192, 6144]
    (2, 1000, 2048, 4),         # a ragged last block, four taps
    (1, 8, 128, 3),             # shorter than a 16-row tile
], ids=["cell-2x8192x6144", "ragged-L1000-K4", "L8"])
def test_short_conv_fwd_bwd_compiles(chip, batch, length, d, taps):
    """The gated short convolution's two kernels, each a Mosaic call under
    its own name, through the operator's custom VJP."""
    from autodist_tpu.ops.short_conv import gated_short_conv

    def loss(bcu, w):
        return gated_short_conv(bcu, w, "pallas").astype(jnp.float32).sum()

    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1)), chip,
                          ((batch, length, 3 * d), jnp.bfloat16),
                          ((d, taps), jnp.float32))
    assert "tpu_custom_call" in text
    assert "short_conv_fwd" in text and "short_conv_bwd" in text


@pytest.mark.parametrize("length,dtype,window", [
    (8192, jnp.bfloat16, False), (8192, jnp.float32, False),
    (1000, jnp.bfloat16, False), (8192, jnp.bfloat16, True),
    (8192, jnp.float32, True), (1000, jnp.bfloat16, True)],
    ids=["cell-1x8192x6144", "cell-layer-0-f32", "ragged-L1000", "cell-window",
         "cell-layer-0-f32-window", "ragged-window"])
def test_conv_silu_fwd_bwd_compiles_at_the_nemotron_cell_shape(chip, length,
                                                               dtype, window):
    """nemotron-pretrain-8k's call: the ungated convolution with bias and SiLU
    over 6,144 channels (3 channel blocks of 2,048), four taps, in bfloat16
    and, for layer 0, float32: two Mosaic calls under their own names through
    the operator's custom VJP. ``window``: as the cell hands it since PR 49,
    columns 4,096 : 10,240 of ``in_proj``'s ``[z | xBC | dt]`` (10,304 wide,
    half a lane tile past a whole one) read where they lie: no slice of
    6,144 columns is written first."""
    from autodist_tpu.ops.short_conv import conv_silu

    def loss(x, w, b):
        return conv_silu(x, w, b, "pallas", at=4096 if window else 0
                         ).astype(jnp.float32).sum()

    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 2)), chip,
                          ((1, length, 10304 if window else 6144), dtype),
                          ((6144, 4), jnp.float32), ((6144,), jnp.float32))
    assert "tpu_custom_call" in text
    assert "conv_silu_fwd" in text and "conv_silu_bwd" in text
    if window:      # (the layer's test holds that nothing is laid out anew:
        # here the sum of the loss asks for a layout of its own)
        assert not re.search(rf"\[1,{length},6144\]\S* slice\(", text)


@pytest.mark.parametrize("length,y_dtype,z_dtype", [
    (8192, jnp.bfloat16, jnp.bfloat16), (8192, jnp.bfloat16, jnp.float32),
    (1000, jnp.bfloat16, jnp.bfloat16)],
    ids=["cell-1x8192x4096", "cell-layer-0-f32", "ragged-L1000"])
def test_gated_norm_fwd_bwd_compiles_at_the_nemotron_cell_shape(chip, length,
                                                                y_dtype, z_dtype):
    """nemotron-pretrain-8k's call: the scan's 4,096 columns of ``y`` gated by
    the first 4,096 of ``in_proj``'s 10,304 and normed over 8 runs of 512, in
    bfloat16 and, for layer 0, a float32 ``z`` and result: two Mosaic calls
    under their own names, and no float32 copy of the rows beside them."""
    from autodist_tpu.ops.gated_norm import gated_norm

    def loss(y, z, scale):
        return gated_norm(y, z, scale, 8, 1e-5, z_dtype, "pallas"
                          ).astype(jnp.float32).sum()

    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 2)), chip,
                          ((1, length, 4096), y_dtype),
                          ((1, length, 10304), z_dtype), ((4096,), jnp.float32))
    assert "gated_norm_fwd" in text and "gated_norm_bwd" in text
    assert f"f32[{length},8,512]" not in text and "[1024,8,8,512]" not in text


def test_fused_xent_forward_compiles_at_lm1b_vocab(chip):
    """lm1b's exact 793,471-word vocabulary, softmax_w layout, forward."""
    def nll(h, w, targets):
        return fx.fused_softmax_xent(h, w, targets, w_layout="vd")

    text = _compiled_text(nll, chip, ((_XENT_ROWS, 1024), jnp.bfloat16),
                          ((793_471, 1024), jnp.float32),
                          ((_XENT_ROWS,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kernel", ["fused_xent", "flash_attention"])
def test_kernels_compile_sharded_over_four_chips(topology, kernel):
    """The compiler cannot partition a Mosaic kernel: under a mesh of several
    devices the ops run it per device (``parallel.mesh.per_device``), the
    batch split over the data axes, a model-sharded table gathered and its
    gradient summed."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from autodist_tpu.parallel.mesh import build_mesh
    from autodist_tpu.parallel.plan import DP_AXES

    mesh = build_mesh(axes={"model": 2, "data": 2}, devices=topology.devices)

    def struct(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    if kernel == "fused_xent":
        def loss(h, w, targets):
            return fx.fused_softmax_xent(h, w, targets).mean()

        fn = jax.value_and_grad(loss, argnums=(0, 1))
        args = (struct((_XENT_ROWS, 512), jnp.bfloat16, P(DP_AXES, None)),
                struct((512, 32_000), jnp.float32, P(None, "model")),
                struct((_XENT_ROWS,), jnp.int32, P(DP_AXES)))
    else:
        def loss(q, k, v):
            return fa.flash_attention(q, k, v).astype(jnp.float32).sum()

        fn = jax.value_and_grad(loss, argnums=(0, 1, 2))
        args = (struct((4, 2048, 8, 64), jnp.bfloat16, P(DP_AXES)),) * 3
    with mesh:
        text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    if kernel == "fused_xent":   # the table's gradient crosses devices
        assert "all-reduce" in text or "reduce-scatter" in text


def _relayouts(text: str, elements: set):
    """The ``copy``, ``transpose`` and ``reshape`` instructions of a compiled
    text whose result holds one of ``elements`` values: whole arrays laid out
    anew. (A reshape that moves nothing is spelled ``bitcast`` there, a move
    to another memory space ``copy-start`` / ``copy-done``.)"""
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([0-9,]+)\]\S* "
                     r"(copy|transpose|reshape)\(", line)
        if m and int(np.prod([int(d) for d in m.group(1).split(",")])) in elements:
            found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("length,chunks,dtype", [
    (16384, 128, jnp.bfloat16), (1000, 8, jnp.bfloat16), (16384, 128, jnp.float32),
], ids=["cell-1x16384", "ragged-L1000", "cell-float32-x"])
def test_selective_scan_fwd_bwd_compiles_at_the_jamba_cell_shape(chip, length,
                                                                 chunks, dtype):
    """jamba2-sharded4-16k's call, a chip's share: one sequence of 5,120
    channels and 16 states, chunks of 128, bfloat16 ``x`` (float32 as the
    precise first layer hands it) and float32 ``dt``, ``B``, ``C``: the
    forward and the backward kernel, each a Mosaic call under its own name,
    through the operator's custom VJP (``B`` and ``C`` as SMEM scalars, a
    token's 1,024 channels one sublane-strided row of a ``[128, 1024]``
    block, 28 MiB of scoped VMEM in the backward); a length the chunk does
    not divide is padded. The kernels take ``x``, ``dt``, ``dy`` and hand
    back ``y``, ``dx``, ``ddt`` as ``[1, L, 5120]`` where XLA holds them: no
    ``copy``, ``transpose`` or non-bitcast ``reshape`` of an array of that
    size (``[1, L, 40, 128]`` is another tiling: 94 ms a step of the cell
    before PR 44), the ragged length's own pad and slice aside."""
    from autodist_tpu.ops.selective_scan import selective_scan

    def loss(x, dt, a, b, c, d):
        return selective_scan(x, dt, a, b, c, d, chunk=128,
                              impl="pallas").astype(jnp.float32).sum()

    wide, narrow = (1, length, 5120), (1, length, 16)
    text = _compiled_text(jax.value_and_grad(loss, argnums=tuple(range(6))), chip,
                          (wide, dtype), (wide, jnp.float32),
                          ((5120, 16), jnp.float32), (narrow, jnp.float32),
                          (narrow, jnp.float32), ((5120,), jnp.float32))
    assert "tpu_custom_call" in text
    assert "selective_scan_fwd" in text and "selective_scan_bwd" in text
    assert f"f32[1,{chunks},16,40,128]" in text      # one [E, N] state a chunk
    assert f"f32[1,{chunks * 128},5120,16]" not in text     # never one a token
    assert not _relayouts(text, {length * 5120, chunks * 128 * 5120})


def _whole_all_reduces(text: str, min_elements: int):
    """The all-reduces of ``min_elements`` or more whose result is used
    otherwise than by a ``dynamic-slice``: a reduction that leaves the leaf
    whole on every chip. (This compiler spells a reduce-scatter as an
    all-reduce of the padded leaf with the share sliced out.)"""
    lines = text.splitlines()
    whole = []
    for line in lines:
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*?) all-reduce(?:-start)?\(", line)
        if not m:
            continue
        sizes = [int(np.prod([int(dim) for dim in dims.split(",") if dim]))
                 for dims in re.findall(r"[a-z0-9]+\[([0-9,]*)\]", m.group(2))]
        if max(sizes) < min_elements:
            continue
        users = [u for u in lines if re.search(
            r"[(, ]%" + re.escape(m.group(1)) + r"[,)]", u)]
        # several leaves reduced as one tuple are not sliced either
        if len(sizes) > 1 or not users \
                or not all(" dynamic-slice(" in u for u in users):
            whole.append((m.group(1), max(sizes)))
    return whole


def test_fully_sharded_step_gathers_weights_and_scatters_gradients(topology):
    """A two-matrix step under ``strategy.FullySharded`` compiled for the four
    described chips: every large leaf arrives as a quarter, the step gathers
    the weights (as bfloat16: the cast moves before the gather) and no
    all-reduce leaves a large gradient whole; under ``AllReduce`` the same
    step all-reduces both whole."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from autodist_tpu import ResourceSpec
    from autodist_tpu.model_spec import ModelSpec
    from autodist_tpu.parallel.mesh import build_mesh, constrain_batch
    from autodist_tpu.parallel.plan import ShardingPlan
    from autodist_tpu.runner import DistributedRunner
    from autodist_tpu.strategy import AllReduce, FullySharded

    d, f, rows = 1024, 2048, 4096

    def loss(p, b):
        h = constrain_batch(b["x"]).astype(jnp.bfloat16)
        a = jnp.tanh(h @ p["up"].astype(jnp.bfloat16))
        out = constrain_batch((a @ p["down"].astype(jnp.bfloat16)))
        return jnp.mean(jnp.square(out.astype(jnp.float32))) + jnp.sum(p["bias"])

    params = {"up": jax.ShapeDtypeStruct((d, f), jnp.float32),
              "down": jax.ShapeDtypeStruct((f, d), jnp.float32),
              "bias": jax.ShapeDtypeStruct((d,), jnp.float32)}
    batch = {"x": np.zeros((rows, d), np.float32)}
    spec = ResourceSpec(resource_info={
        "nodes": [{"address": "localhost", "tpus": 4, "chief": True}],
        "mesh": {"data": 4}})
    mesh = build_mesh(axes={"data": 4}, devices=list(topology.devices)[:4])

    def compiled_text(builder):
        model_spec = ModelSpec.from_loss_fn(loss, params, batch)
        strategy = builder.build(model_spec, spec)
        runner = DistributedRunner(
            strategy, model_spec, loss, optax.adamw(1e-3), mesh=mesh,
            plan=ShardingPlan.from_strategy(strategy, model_spec))
        state = runner._abstract_state(params)
        runner._ensure_state_shardings(state)
        state = jax.tree_util.tree_map(
            lambda leaf, sharding: jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=sharding),
            state, runner._state_shardings)
        x = jax.ShapeDtypeStruct((rows, d), jnp.float32,
                                 sharding=NamedSharding(mesh, P("data")))
        with mesh:
            return runner, runner._build_step(None).lower(
                state, {"x": x}).compile().as_text()

    runner, text = compiled_text(FullySharded())
    assert runner._state_shardings.params["up"].spec == P("data", None)
    assert runner._state_shardings.opt_state[0].mu["down"].spec == P("data", None)
    assert runner._state_shardings.params["bias"].spec == P()
    assert "all-gather(" in text and " all-to-all(" not in text
    assert _whole_all_reduces(text, d * f) == []
    _, replicated = compiled_text(AllReduce())
    assert len(_whole_all_reduces(replicated, d * f)) >= 1
    assert "all-gather(" not in replicated


@pytest.mark.parametrize("sliding", [True, False], ids=["sliding-sink", "full"])
def test_flash_with_a_sink_and_a_128_key_window_compiles_at_the_mimo_cell_shape(
        chip, sliding):
    """mimo-sharded4-8k's two calls, 1 x 8,192 x 64 query heads, keys 192 over
    values 128, as the model hands them (q and k ``[B, L, heads, 192]``, v its
    projection's rows): a sliding layer's (window 128: the walk fitted to the
    band, ``[256, 128]`` tiles cut at run-time starts out of the resident K/V
    and out of the resident q and dO, 8 KV heads, the heads' sinks whole in
    SMEM: kernels ``flash_sink_*``) and a full layer's (4 KV heads, no sink:
    the plain names). K/V of a head stay resident (3 MiB of keys) and the
    backward is one pass (6 MiB of dQ), both inside the scoped VMEM the
    kernels ask for."""
    b, length, h, kv = 1, 8192, 64, 8 if sliding else 4
    shapes = [((b, length, h, 192), jnp.bfloat16),
              ((b, length, kv, 192), jnp.bfloat16),
              ((b, length, kv * 128), jnp.bfloat16)]
    if sliding:
        shapes.append(((h,), jnp.float32))

    def loss(q, k, v, sink=None):
        return fa.flash_attention(
            q, k, v, causal=True, window=128 if sliding else None,
            heads=(h, kv), sink=sink).astype(jnp.float32).sum()

    text = _compiled_text(
        jax.value_and_grad(loss, argnums=tuple(range(len(shapes)))), chip, *shapes)
    assert fa._forward_blocks(length, length, 192, 2, None, None) == \
        (512, 8192, 512)
    assert length * 192 * 4 <= fa._RESIDENT_DQ_BYTES
    names = ("flash_sink_fwd", "flash_sink_bwd_dkv") if sliding \
        else ("flash_fwd", "flash_bwd_dkv")
    for name in names:
        assert name in text
    assert ("flash_sink" in text) == sliding
    assert "bwd_dq" not in text
    # the fitted walk: four masked tiles a q block, none plain, all but the
    # first of a block issued under another's softmax; the backward's likewise
    if sliding:
        from autodist_tpu import telemetry
        assert fa._band_span(128, 512, 8192, 512) == 256
        assert [telemetry.gauge(f"flash.fwd.tiles_{k}").value for k in
                ("plain", "masked", "overlapped")] == [0, 64, 48]
        assert [telemetry.gauge(f"flash.bwd.tiles_{k}").value for k in
                ("plain", "masked")] == [0, 64]


def test_fully_sharded_mimo_step_gathers_the_expert_banks_outside_the_pass_loop(
        topology):
    """A two-layer MiMo share (the dense layer, a sliding expert layer; banks
    of 8 x 512 x 256) under ``strategy.FullySharded`` compiled for the four
    described chips: the banks arrive as quarters and are gathered as
    bfloat16 OUTSIDE the run-time loop over the passes past the first (whose
    trip count differs a chip: a collective inside it would hang the host),
    and no all-reduce leaves a large gradient whole (the form of
    ``test_fully_sharded_step_gathers_weights_and_scatters_gradients``)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from autodist_tpu import ResourceSpec
    from autodist_tpu.model_spec import ModelSpec
    from autodist_tpu.models import mimo_v2
    from autodist_tpu.parallel.mesh import build_mesh
    from autodist_tpu.parallel.plan import ShardingPlan
    from autodist_tpu.runner import DistributedRunner
    from autodist_tpu.strategy import FullySharded

    cfg = mimo_v2.MimoV2Config(
        vocab_size=1024, d_model=512, n_heads=4, n_kv_heads=1, swa_n_kv_heads=2,
        head_dim=192, v_head_dim=128, layer_pattern=(0, 1), moe_layer_freq=(0, 1),
        d_ff=1024, d_expert=256, n_experts_routed=32, experts_held=8, top_k=4,
        rows_bound=256, window=128, max_len=1024, attention_impl="flash",
        fused_head=True, remat=True)
    model = mimo_v2.MimoV2(cfg)
    params = jax.eval_shape(lambda key: mimo_v2.init_params(cfg, rng=key)[1],
                            jax.random.PRNGKey(0))
    loss = mimo_v2.make_loss_fn(model)
    batch = {"tokens": np.zeros((4, 1025), np.int32)}
    spec = ResourceSpec(resource_info={
        "nodes": [{"address": "localhost", "tpus": 4, "chief": True}],
        "mesh": {"data": 4}})
    mesh = build_mesh(axes={"data": 4}, devices=list(topology.devices)[:4])
    model_spec = ModelSpec.from_loss_fn(loss, params, batch)
    strategy = FullySharded().build(model_spec, spec)
    runner = DistributedRunner(
        strategy, model_spec, loss,
        mimo_v2.make_optimizer(1e-3, cfg.load_balance_coeff), mesh=mesh,
        plan=ShardingPlan.from_strategy(strategy, model_spec))
    state = runner._abstract_state(params)
    runner._ensure_state_shardings(state)
    assert runner._state_shardings.params["block_1"]["moe"]["up"].spec == \
        P("data", None, None)
    state = jax.tree_util.tree_map(
        lambda leaf, sharding: jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=sharding),
        state, runner._state_shardings)
    tokens = jax.ShapeDtypeStruct((4, 1025), jnp.int32,
                                  sharding=NamedSharding(mesh, P("data")))
    with mesh:
        text = runner._build_step(None).lower(
            state, {"tokens": tokens}).compile().as_text()
    # the banks are gathered, as bfloat16 (a quarter [2, 512, 256] in, the
    # whole [8, 512, 256] out), and the sink's kernels are in the step
    gathers = [line for line in text.splitlines()
               if re.search(r"= bf16\[8,(512,256|256,512)\]\S* all-gather", line)]
    assert gathers, "no bfloat16 gather of an expert bank"
    assert "flash_sink_fwd" in text and "moe_gmm_fwd" in text
    # no collective inside a while loop's body or condition: the loops of
    # the later passes run another number of times on every chip
    bodies = set(re.findall(r"(?:body|condition)=%?([\w.\-]+)", text))
    assert bodies, "the pass loops are gone: rows_bound no longer forces them"
    computation, inside = None, []
    for line in text.splitlines():
        start = re.match(r"\s*(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{\s*$", line)
        if start:
            computation = start.group(1)
        elif computation in bodies and re.search(
                r" (all-gather|all-reduce|reduce-scatter|collective-permute|"
                r"all-to-all)(-start)?\(", line):
            inside.append((computation, line.strip()[:120]))
    assert inside == []
    assert _whole_all_reduces(text, 8 * 512 * 256) == []


def test_eva_attention_compiles_at_the_evabyte_cell_shape(chip):
    """evabyte-pretrain-16k's call, 1 x 16,384 x 8 heads held of 128, window
    2,048, chunk 16, as the model hands it (q and k rotated ``[B, L, heads,
    128]``, v its projection's rows reshaped): the forward with a window's K
    and V and the (batch, head)'s 1,024 summaries resident, the one-pass
    backward with a window's q, dO and float32 dQ resident and the
    summaries' float32 gradients resident across the windows, both inside
    the scoped VMEM the kernels ask for; the pooling stays XLA's."""
    from autodist_tpu import telemetry
    from autodist_tpu.ops import eva_attention as ea
    b, length, h, d = 1, 16384, 8, 128
    rows = ((b, length, h, d), jnp.bfloat16)
    vectors = ((h, d), jnp.float32)

    def loss(q, k, v, phi, mu):
        return ea.eva_attention(q, k, v, phi, mu, window=2048,
                                chunk=16).astype(jnp.float32).sum()

    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)),
                          chip, rows, rows, rows, vectors, vectors)
    assert "eva_fwd" in text and "eva_bwd" in text
    assert text.count("tpu_custom_call") == 2 and "flash_fwd" not in text
    assert ea._blocks(2048, 16) == (512, 512, 128)
    assert telemetry.gauge("eva.windows").value == 8
    assert telemetry.gauge("eva.summaries").value == 1024


@pytest.mark.parametrize("mixer", ["kda", "mla"])
@pytest.mark.parametrize("heads", [16, 8], ids=["16-heads", "8-heads-fallback"])
def test_ling_mixers_compile_at_the_ling_cell_shape(chip, heads, mixer):
    """ling-pretrain-8k's two mixers as the model runs them, 1 x 8,192 x 2,560
    with 16 of a layer's 32 heads held (and the fallback's 8), forward and
    backward: a KDA layer is ``kda_fwd`` / ``kda_bwd`` over ``[1, 8192, heads x
    128]`` rows (128 chunks of 64 a head, the head's float32 state in VMEM)
    behind three ``conv_silu`` calls at ``heads x 128`` channels without a
    bias; the gated latent layer is flash at keys 192 over values 128 with the
    shared rotary key. What XLA keeps between the projections and the kernels
    lays no ``[1, 8192, heads, 128]`` array out: the rows stay rows."""
    import flax.linen as nn

    from autodist_tpu import telemetry
    from autodist_tpu.models import bailing_hybrid as bh
    from autodist_tpu.models.deepseek_v3 import LatentAttention
    cfg = bh.BailingHybridConfig(
        n_layers=6, heads_held=heads, attention_impl="flash", kda_impl="pallas")
    module: nn.Module = (bh.KimiDeltaAttention(cfg) if mixer == "kda" else
                         LatentAttention(cfg, heads_held=heads, head_gate=True))
    h = jax.ShapeDtypeStruct((1, 8, cfg.d_model), jnp.bfloat16)
    shapes = jax.eval_shape(lambda x: module.init(jax.random.PRNGKey(0), x), h)

    def loss(params, h):
        return module.apply(params, h).astype(jnp.float32).sum()

    leaves, tree = jax.tree_util.tree_flatten(shapes)
    text = _compiled_text(
        lambda *args: jax.value_and_grad(loss, argnums=(0, 1))(
            jax.tree_util.tree_unflatten(tree, args[:-1]), args[-1]),
        chip, *((x.shape, x.dtype) for x in leaves),
        ((1, 8192, cfg.d_model), jnp.bfloat16))
    if mixer == "kda":
        assert "kda_fwd" in text and "kda_bwd" in text
        assert "conv_silu_fwd" in text and "conv_silu_bwd" in text
        assert text.count("tpu_custom_call") == 8       # 3 + 1 forward, 3 + 1 backward
        assert telemetry.gauge("kda.chunks").value == 128
        assert telemetry.gauge("kda.heads_held").value == heads
        assert telemetry.gauge("kda.state_kept_bytes").value == \
            128 * heads * 128 * 128 * 4
        assert not re.search(rf"\[1,8192,{heads},128\]", text)
    else:
        assert "flash_fwd" in text and "flash_bwd_dkv" in text
        assert telemetry.gauge("attention.heads_held").value == heads


def test_ling_kda_mixer_compiles_to_float32s_precision(chip):
    """The first layers' second forward (``models/bailing_hybrid.py``
    ``PRECISE_LAYERS``): the KDA mixer on float32 rows, ``kda_fwd`` and the
    three convolutions on float32 operands, every product of the kernel at
    full precision; no backward (the derivative is the ordinary layer's)."""
    from autodist_tpu.models import bailing_hybrid as bh
    cfg = bh.BailingHybridConfig(n_layers=6, heads_held=16, kda_impl="pallas")
    module = bh.KimiDeltaAttention(cfg)
    shapes = jax.eval_shape(
        lambda x: module.init(jax.random.PRNGKey(0), x),
        jax.ShapeDtypeStruct((1, 8, cfg.d_model), jnp.float32))
    leaves, tree = jax.tree_util.tree_flatten(shapes)
    text = _compiled_text(
        lambda *args: module.apply(
            jax.tree_util.tree_unflatten(tree, args[:-1]), args[-1], True),
        chip, *((x.shape, x.dtype) for x in leaves),
        ((1, 8192, cfg.d_model), jnp.float32))
    assert "kda_fwd" in text and "kda_bwd" not in text
    assert text.count("tpu_custom_call") == 4


def test_fused_xent_compiles_at_the_ling_cell_shape(chip):
    """ling-pretrain-8k's sliced head: 8,192 rows of 2,560 against 19,648
    vocabulary rows, the widest hidden size the fused head has been given."""
    def loss(h, w, targets):
        return fx.fused_softmax_xent(h, w, targets).mean()

    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1)), chip,
                          ((8192, 2560), jnp.bfloat16),
                          ((2560, 19_648), jnp.float32), ((8192,), jnp.int32))
    assert "xent_fwd" in text and "xent_bwd_dw" in text
