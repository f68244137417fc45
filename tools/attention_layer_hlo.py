#!/usr/bin/env python3
"""What XLA puts around a cell's flash kernels, read without the chip.

Compiles one attention layer of a cell's model, forward and backward at the
cell's own shape (under the layer's checkpoint where the cell has one), for
a v5e that is described and not attached, and lists the device operations of
the optimized HLO that are neither kernels nor matmuls and write at least
``--least`` MB, with the sum of what they write: the copies, transposes,
slices and elementwise passes between the projections and the kernels.

    JAX_PLATFORMS=cpu python tools/attention_layer_hlo.py kanana
    JAX_PLATFORMS=cpu python tools/attention_layer_hlo.py olmoe --root .archive_check/parent

A count of bytes, not a time, and never reported as one: it ranks two ways
of handing the kernels their operands (PR 41: Kanana's layer wrote 7,105 MB
around its kernels on the parent and 3,479 on the change, and the cell's
step fell by 81 ms of 938; OLMoE's 3,087 with q and k transposed and 3,691
with them handed as rows, which is why they are not) and shows WHICH
operation pays (a float32 copy of q into the transposed layout, both
shifted copies of a rotary turn written out). Seven seconds a layer here.
``nemotron-mamba`` (PR 49) is that cell's Mamba-2 layer, the first that is not
attention: 2,024 MB on its parent, 361 since, of which 201 are two
``dynamic-update-slice`` fusions counted at their array's size that write 34
in place (``--xla_dump_to`` names the buffers: one offset for all three).
``ling-kda`` / ``ling-mla`` (PR 52) are ``ling-pretrain-8k``'s two mixers at 16
of 32 heads held.
"""

import argparse
import importlib
import os
import re
import sys

CELLS = ("kanana", "nemotron", "nemotron-mamba", "olmoe", "trinity-sliding",
         "trinity-full", "ling-kda", "ling-mla")
_BYTES = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1, "s8": 1, "u8": 1}
_QUIET = ("parameter", "constant", "get-tuple-element", "tuple", "bitcast",
          "copy-start", "copy-done", "slice-start", "slice-done")


def layer(cell: str):
    """``(apply(params, x), init module, (B, L, d_model))`` of ``cell``."""
    import flax.linen as nn
    import jax.numpy as jnp

    from autodist_tpu.models.common import keeping
    if cell == "kanana":
        m = importlib.import_module("autodist_tpu.models.deepseek_v3")
        cfg = m.DeepseekV3Config(
            vocab_size=16032, d_model=2048, n_layers=6, n_heads=32,
            attention_impl="flash", remat=True, dtype=jnp.bfloat16,
            rope_theta=1e6)
        return (nn.remat(m.LatentAttention, policy=keeping(m.KEPT))(cfg),
                m.LatentAttention(cfg), (1, 16384, 2048))
    if cell.startswith("ling"):
        m = importlib.import_module("autodist_tpu.models.bailing_hybrid")
        cfg = m.BailingHybridConfig(n_layers=6, heads_held=16, remat=True,
                                    attention_impl="flash", kda_impl="pallas")
        if cell == "ling-kda":
            return (nn.remat(m.KimiDeltaAttention, policy=keeping(m.KEPT))(cfg),
                    m.KimiDeltaAttention(cfg), (1, 8192, 2560))
        kind = dict(heads_held=16, head_gate=True)
        return (nn.remat(m.LatentAttention, policy=keeping(m.KEPT))(cfg, **kind),
                m.LatentAttention(cfg, **kind), (1, 8192, 2560))
    if cell.startswith("nemotron"):
        m = importlib.import_module("autodist_tpu.models.nemotron_h")
        cfg = m.NemotronHConfig(attention_impl="flash", remat=True,
                                ssm_impl="pallas")
        mixer = m.Mamba2 if cell == "nemotron-mamba" else m.GroupedAttention
        return (nn.remat(mixer, policy=keeping(m.KEPT))(cfg), mixer(cfg),
                (1, 8192, 2688))
    if cell == "olmoe":
        m = importlib.import_module("autodist_tpu.models.olmoe")
        module = m.QKNormAttention(m.OlmoeConfig(attention_impl="flash"))
        return module, module, (4, 4096, 2048)
    m = importlib.import_module("autodist_tpu.models.afmoe")
    module = m.GatedAttention(m.AfmoeConfig(attention_impl="flash"),
                              m.SLIDING if cell.endswith("sliding") else m.FULL)
    return module, module, (1, 8192, 2048)


def compiled_text(cell: str) -> str:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    fa = importlib.import_module("autodist_tpu.ops.flash_attention")
    fa._use_interpret = lambda: False      # compile the kernels, here too
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    module, init, (b, length, d) = layer(cell)
    params = jax.eval_shape(lambda: init.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128, d), jnp.bfloat16)))
    on_chip = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip)  # noqa: E731
    loss = lambda p, x: module.apply(p, x).astype(jnp.float32).sum()  # noqa: E731
    return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        jax.tree.map(on_chip, params),
        on_chip(jax.ShapeDtypeStruct((b, length, d), jnp.bfloat16))
    ).compile().as_text()


def written(shape: str) -> int:
    total = 0
    for dtype, dims in re.findall(r"(bf16|f32|s32|u32|pred|s8|u8)\[([0-9,]*)\]",
                                  shape):
        n = 1
        for dim in filter(None, dims.split(",")):
            n *= int(dim)
        total += n * _BYTES[dtype]
    return total


def around_the_kernels(text: str, least_mb: float):
    """``[(MB written, op, fusion kind, name, shape)]`` of the entry
    computation's operations of ``least_mb`` or more, and the MB those
    that are neither kernels nor matmuls write."""
    rows, total = [], 0.0
    for line in text[text.index("ENTRY"):].splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\(", line)
        if not m or m.group(3) in _QUIET:
            continue
        name, shape, op = m.groups()
        mb = written(shape) / 1e6
        if mb < least_mb:
            continue
        kind = re.search(r"kind=(k\w+)", line)
        rows.append((mb, op, kind.group(1) if kind else "", name,
                     re.sub(r":T[^}]*", "", shape)[:90]))
        if op != "custom-call" and "convolution" not in name \
                and (not kind or kind.group(1) != "kOutput"):
            total += mb
    return rows, total


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("cells", nargs="+", choices=CELLS)
    parser.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="checkout to import autodist_tpu from")
    parser.add_argument("--least", type=float, default=30.0,
                        help="MB an operation writes to be listed")
    args = parser.parse_args(argv)
    sys.path.insert(0, os.path.abspath(args.root))
    for cell in args.cells:
        rows, total = around_the_kernels(compiled_text(cell), args.least)
        for mb, op, kind, name, shape in rows:
            print(f"{mb:8.1f} MB  {op:16s} {kind:8s} {name:40s} {shape}")
        print(f"{cell}: {total:.0f} MB written by operations that are "
              f"neither kernels nor matmuls (root {args.root})")


if __name__ == "__main__":
    main()
