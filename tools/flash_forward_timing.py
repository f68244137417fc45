#!/usr/bin/env python3
"""Time the flash and fused-head kernels alone, on the chip, at named shapes.

The instrument behind the block choices in ``ops/flash_attention.py``
(``_forward_blocks``, ``_backward_blocks``) and ``ops/fused_xent.py``
(``_fit_blocks``): one jitted ``_flash_forward``, carry step or
``_flash_backward`` (on residuals prepared outside the trace), or the head's
``_forward`` / ``_backward``, per shape, run under the profiler, and the
kernels' own device time read from the trace's ``XLA Ops`` events named
``flash_fwd`` / ``flash_carry`` / ``flash_bwd_dkv`` + ``flash_bwd_dq``
(summed, and each under ``parts``) / ``xent_fwd`` / ``xent_bwd_dh`` /
``xent_bwd_dw``; the host clock around the whole call, transposes included,
is printed beside it. One JSON line a measurement on standard output.
``xent-bwd*`` shapes time the head's whole backward twice, as the rule runs
it (one pass under the name ``xent_bwd_dw`` where the rule gives one) and as
the two kernels, side by side; ``--check`` compares dh, dw and db of the two
on the chip instead of timing them (the one pass reads dw back through an
aliased buffer, in an order only the chip's own pipeline shows), and exits
1 where they differ.

    python tools/flash_forward_timing.py                     # every shape
    python tools/flash_forward_timing.py --shapes cell,l4096,bwd-cell,bwd-olmoe
    python tools/flash_forward_timing.py --blocks 512,512,128 --blocks 256,1024,256
    python tools/flash_forward_timing.py --bwd-blocks 512,1024
    python tools/flash_forward_timing.py --shapes xent-fwd,xent-dh,xent-dw --xent-blocks 1024,512
    python tools/flash_forward_timing.py --shapes xent-bwd,xent-bwd-gpt2,xent-bwd-trinity --xent-blocks 1024,256
    python tools/flash_forward_timing.py --shapes xent-bwd,xent-bwd-gpt2,xent-bwd-trinity,xent-bwd-v3 --check
    python tools/flash_forward_timing.py --root .archive_check/parent   # another checkout
    python tools/flash_forward_timing.py --shapes mla-padded,mla-assembled,mla-shared,bwd-mla-padded,bwd-mla-assembled,bwd-mla-shared [--resident-dq-bytes 4194304]
    python tools/flash_forward_timing.py --shapes mla-shared,trinity-full,trinity-win,nemotron,lfm2,cell,l8192 [--walk-groups 0] [--blocks 512,2048,512]

``mla-*`` (PR 37): latent attention's call at kanana-pretrain-16k's shape, 1 x
16,384 x 32 heads, keys 192 wide (128 a head + 64 rotary columns all heads
share), values 128, in three forms: ``padded`` (v padded to 192 through the
one-width kernels: runs on any checkout), ``assembled`` (k put together in
memory, 32 copies of the rotary columns, dK's last 64 columns summed over
the heads afterwards; the kernels take the two widths) and ``shared`` (the
rotary columns a second operand, the score tile two products). Each times
what the form adds around the kernels too (``device_ms_all``: every device
operation of a call). The backward runs in one pass (12 MiB of dQ a head);
``--resident-dq-bytes 4194304`` times the two kernels of the split path, which
PR 29's limit gave this width.

``nemotron`` / ``lfm2`` (PR 39): the two cells' forward calls no other name
covered, so one command times every cell's forward; ``l32768-d128``: 8 MiB of
K a head, past what the forward keeps resident. ``flash.fwd.tiles_overlapped``
(the plain tiles whose score product is issued under another tile's softmax)
is printed with the other gauges. ``--walk-groups 4,2`` overrides the sizes
of the straight-line blocks the forward's walk takes its plain tiles in (0:
none, every tile a chain of its own), ``--group-q-chunks N`` the pieces of
the queries a tile is cut into inside one.

Since PR 41 the ``mla-*``, ``trinity-*``, ``nemotron`` and ``olmoe`` shapes
(and their ``bwd-``) print ``device_ms_all`` beside the kernel's own time:
every device operation of a call, so what XLA puts around the kernels
(transposes, slices, the sums over a group) is read stand-alone, with the
gauges ``flash.fwd.operands_relaid`` / ``flash.bwd.operands_relaid``; and
they hand the call its operands as the cell's model does (``ROWS``): the
operands that go from a projection into the call untouched as ``[B, L,
heads * D]`` rows, which a checkout that takes rows reads in place, the
others ``[B, L, heads, D]`` (a checkout older than PR 41 is handed the
reshape, as its models did). ``mla-packed`` / ``bwd-mla-packed``: the
``shared`` form with ``k_nope`` and ``v`` one ``[k_nope | v]`` array, as the
layer's ``kv_up`` product hands them and takes their gradient (an older
checkout cuts them apart inside the call and puts the gradients together).
What the stand-alone call cannot show is what XLA does to the producers
and consumers of these arrays in a cell (PR 41: most of Kanana's gain was
there); compile the layer for a described v5e and read the HLO for that.

``mimo-swa`` / ``mimo-full`` and their ``bwd-`` (PR 46): MiMo-V2.5's two
calls at ``mimo-sharded4-8k``'s shape, 1 x 8,192 x 64 query heads, keys 192
over values 128, handed as the model hands them (q and k ``[B, L, heads,
192]``, turned since their projections; v its projection's rows): a sliding
layer's (window 128, 8 KV heads, a sink a head: the kernels' device names are
``flash_sink_*``) and a full layer's (4 KV heads, no sink). ``--check``
compares the result and dq, dk, dv and d sink of both with the dot path with
the sink as a concatenated column, ON THE CHIP at 2,048 positions, and exits
1 where they differ. Since PR 47 ``--blocks`` / ``--bwd-blocks`` reach these
shapes (the forward that makes the backward's residuals is asked for the
backward's q tile), and every causal flash shape prints ``fill_pct`` beside
its time and its ``tiles_*`` gauges: of the pairs of the score tiles a
(batch, head) runs, the share the mask keeps (``mimo-swa``: 12.8 under 512 x
512 tiles, 49.6 under the walk fitted to the band; ``--root`` of a checkout
older than the fitted walk reads the former).

``--blocks bq,bk,sub`` overrides the forward's choice, ``--bwd-blocks bq,bk``
the backward's, ``--xent-blocks bn,bv`` the named head kernel's (a checkout
whose kernels run a fixed default takes no override; tiles the compiler
refuses are reported and passed over). Needs the TPU: a time from the CPU's
interpreter says nothing.
"""

import argparse
import importlib
import json
import os
import sys
import tempfile
import time

# name -> (B, L, H, D, causal, kind)
SHAPES = {
    "cell": (8, 1024, 16, 64, True, "fwd"),        # gpt2m-* per chip and call
    "l4096": (4, 4096, 16, 64, True, "fwd"),
    "l8192": (4, 8192, 16, 64, True, "fwd"),
    "d128": (4, 2048, 16, 128, True, "fwd"),
    "noncausal": (8, 1024, 16, 64, False, "fwd"),
    # one ring step with traced offsets: the shard on the diagonal, and a
    # shard wholly before the queries
    "carry-diag": (4, 4096, 16, 64, True, "carry"),
    "carry-visible": (4, 4096, 16, 64, True, "carry"),
    # the backward on a forward's residuals
    "bwd-cell": (8, 1024, 16, 64, True, "bwd"),
    "bwd-l4096": (4, 4096, 16, 64, True, "bwd"),
    "bwd-d128": (4, 2048, 16, 128, True, "bwd"),
    "bwd-noncausal": (8, 1024, 16, 64, False, "bwd"),
    "bwd-olmoe": (4, 4096, 16, 128, True, "bwd"),     # olmoe-pretrain-4k's call
    "bwd-l16384": (1, 16384, 8, 64, True, "bwd"),     # 4 MiB of dQ: PR 29's limit
    "bwd-l16384-d128": (1, 16384, 8, 128, True, "bwd"),    # 8 MiB: one pass since PR 37
    # one ring step of the backward as ``_ring_flash_bwd`` runs it: traced
    # offsets, float32 outputs, 512-row blocks asked for
    "bwd-ring-diag": (4, 4096, 16, 64, True, "ring-bwd"),
    "bwd-ring-visible": (4, 4096, 16, 64, True, "ring-bwd"),
    # trinity-pretrain-8k's calls (B·H 32, D 128, 4 KV heads): a sliding layer
    # (window 2,048), the full layer, and each with K/V repeated in memory to
    # the 32 query heads instead of read through the index map
    "trinity-win": (1, 8192, 32, 128, True, "fwd"),
    "trinity-full": (1, 8192, 32, 128, True, "fwd"),
    "trinity-win-rep": (1, 8192, 32, 128, True, "fwd"),
    "trinity-full-rep": (1, 8192, 32, 128, True, "fwd"),
    "bwd-trinity-win": (1, 8192, 32, 128, True, "bwd"),
    "bwd-trinity-full": (1, 8192, 32, 128, True, "bwd"),
    "bwd-trinity-win-rep": (1, 8192, 32, 128, True, "bwd"),
    "bwd-trinity-full-rep": (1, 8192, 32, 128, True, "bwd"),
    # the two forwards no other name covers (PR 39): nemotron-pretrain-8k's
    # call, 32 query heads over 2 KV heads of 128, and lfm2-pretrain-8k's, 2
    # sequences of 32 query heads over 8 KV heads of 64 (walks to 16 tiles)
    "nemotron": (1, 8192, 32, 128, True, "fwd"),
    "lfm2": (2, 8192, 32, 64, True, "fwd"),
    # past what stays resident (PR 39: 4 MiB of K a head): 8 MiB, streamed
    "l32768-d128": (1, 32768, 8, 128, True, "fwd"),
    "olmoe": (4, 4096, 16, 128, True, "fwd"),         # olmoe-pretrain-4k's call
    "bwd-nemotron": (1, 8192, 32, 128, True, "bwd"),
}
# latent attention's call: name -> form
MLA_V, MLA_SHARED = 128, 64
MLA = {f"{kind}mla-{form}": form for kind in ("", "bwd-")
       for form in ("padded", "assembled", "shared", "packed")}
SHAPES.update({name: (1, 16384, 32, 192, True,
                      "bwd" if name.startswith("bwd-") else "fwd")
               for name in MLA})
# MiMo-V2.5's two calls: name -> form -> (window, KV heads, a sink a head)
MIMO_V = 128
MIMO_FORMS = {"swa": (128, 8, True), "full": (None, 4, False)}
MIMO = {f"{kind}mimo-{form}": form for kind in ("", "bwd-") for form in MIMO_FORMS}
SHAPES.update({name: (1, 8192, 64, 192, True,
                      "bwd" if name.startswith("bwd-") else "fwd")
               for name in MIMO})
# name -> (window, KV heads) where they are not (None, H)
BANDS = {name: (2048 if "-win" in name else None, 32 if name.endswith("-rep") else 4)
         for name in SHAPES if "trinity" in name}
BANDS.update({"nemotron": (None, 2), "bwd-nemotron": (None, 2),
              "lfm2": (None, 8)})
# the calls whose whole device time is printed beside the kernels' own
AROUND = {*MLA, *MIMO, *BANDS, "olmoe", "bwd-olmoe"} - {"lfm2"}
# which of q, k, v the cell's model hands as a projection's rows (PR 41):
# v where q and k are turned or normed a head first, all three in Nemotron
ROWS = {**{name: "v" for name in AROUND - set(MLA)},
        "nemotron": "qkv", "bwd-nemotron": "qkv"}
# the fused head, bfloat16 rows against a float32 table: name -> (N, D, V,
# table layout, kind), at olmoe-pretrain-4k's call, at gpt2m-*'s, a chip and
# call, and at trinity-pretrain-8k's. ``xent-dh`` and ``xent-dw`` both run
# the whole backward and read their own kernel's events; ``xent-bwd`` reads
# both names, as the rule runs the backward and as the two kernels.
# ``xent-bwd-v3``: three vocab blocks of 512, the fewest the one pass takes
# (the block a row block reads back was written three grid steps before).
HEAD_SHAPES = {
    f"xent-{kernel}{cell}": (*shape, f"xent-{kernel}")
    for cell, shape in (("", (16384, 2048, 50304, "dv")),
                        ("-gpt2", (8192, 1024, 50257, "vd")),
                        ("-trinity", (8192, 2048, 25024, "dv")),
                        ("-v3", (4096, 1024, 1536, "dv")))
    for kernel in ("fwd", "dh", "dw", "bwd")
    if kernel == "bwd" or cell in ("", "-gpt2")}
KERNELS = {"fwd": ("flash_fwd",), "carry": ("flash_carry",),
           "bwd": ("flash_bwd_dkv", "flash_bwd_dq"),
           "ring-bwd": ("flash_bwd_dkv", "flash_bwd_dq"),
           "xent-fwd": ("xent_fwd",), "xent-dh": ("xent_bwd_dh",),
           "xent-dw": ("xent_bwd_dw",),
           "xent-bwd": ("xent_bwd_dh", "xent_bwd_dw")}
GAUGES = {"fwd": "flash.fwd.", "bwd": "flash.bwd.",
          **{kind: "xent." for kind in ("xent-fwd", "xent-dh", "xent-dw",
                                        "xent-bwd")}}
# relative L2 distance up to which the one pass agrees with the two kernels:
# dh is rounded to bfloat16 from sums taken in another order over the
# vocabulary, dw and db are float32 sums of the same tile products
CHECK_TOLERANCE = {"dh": 4e-3, "dw": 1e-4, "db": 1e-4}


# relative L2 distance up to which the kernels on bfloat16 operands agree
# with the dot path on the same operands in float32 (2^-8 a rounding of p and
# dS where they enter a product)
MIMO_CHECK_TOLERANCE = 2e-2
MIMO_CHECK_LENGTH = 2048


def kind_of(name: str) -> str:
    return (SHAPES.get(name) or HEAD_SHAPES[name])[-1]


def kernels_of(name: str) -> tuple:
    """The device names a shape's kernels carry: a call with a sink has its
    own."""
    names = KERNELS[kind_of(name)]
    if name in MIMO and MIMO_FORMS[MIMO[name]][2]:
        return tuple(n.replace("flash_", "flash_sink_") for n in names)
    return names


def device_events(trace_dir: str):
    """The ``XLA Ops`` events of every device in the newest trace."""
    from jax.profiler import ProfileData
    found = []
    for root, _, files in os.walk(trace_dir):
        found += [os.path.join(root, f) for f in files if f.endswith(".xplane.pb")]
    data = ProfileData.from_file(max(found, key=os.path.getmtime))
    return [e for plane in data.planes if plane.name.startswith("/device:TPU:")
            for line in plane.lines if line.name == "XLA Ops"
            for e in line.events]


def kernel_ms(trace_dir: str, kernel: str):
    """Durations (ms) of the device events of ``kernel`` in the newest trace."""
    return [e.duration_ns * 1e-6 for e in device_events(trace_dir)
            if e.name.lstrip("%").startswith(kernel)]


def all_ops_ms(trace_dir: str) -> float:
    """Milliseconds of every device operation in the newest trace."""
    return sum(e.duration_ns * 1e-6 for e in device_events(trace_dir))


def takes_rows(fa) -> bool:
    """Whether this checkout's kernels take ``[B, L, heads * D]`` rows."""
    import inspect
    return "heads" in inspect.signature(fa._flash_forward).parameters


def build_mla(fa, name):
    """Latent attention's call in one of its three forms, from the operands
    the layer has (q, a head's 128 key columns, the 64 all heads share, v)
    to what it needs back (the result; the four gradients)."""
    import jax
    import jax.numpy as jnp

    b, length, h, d, causal, kind = SHAPES[name]
    form = MLA[name]
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(keys[0], (b, length, h, d), jnp.bfloat16)
    k_nope = jax.random.normal(keys[1], (b, length, h, d - MLA_SHARED), jnp.bfloat16)
    k_rope = jax.random.normal(keys[2], (b, length, MLA_SHARED), jnp.bfloat16)
    v = jax.random.normal(keys[3], (b, length, h, MLA_V), jnp.bfloat16)
    g = jax.random.normal(keys[4], (b, length, h, MLA_V), jnp.bfloat16)

    packed = form == "packed"
    rows_taken = takes_rows(fa)
    if packed:      # kv_up's output: a head's 128 key columns, then its values
        k_nope = jnp.concatenate([k_nope, v], axis=-1).reshape(b, length, -1)
        v = None
        g = g.reshape(b, length, -1)

    def operands(k_nope, k_rope, v):
        if packed and rows_taken:
            return k_nope, None, {"k_shared": k_rope, "heads": (h, h)}
        if packed:
            k_nope = k_nope.reshape(b, length, h, -1)
            k_nope, v = k_nope[..., :d - MLA_SHARED], k_nope[..., d - MLA_SHARED:]
        if form in ("shared", "packed"):
            return k_nope, v, {"k_shared": k_rope}
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_rope[:, :, None, :], (b, length, h, MLA_SHARED))], axis=-1)
        if form == "padded":
            v = jnp.pad(v, ((0, 0),) * 3 + ((0, d - MLA_V),))
        return k, v, {}

    def forward(q, k_nope, k_rope, v):
        k, v, shared = operands(k_nope, k_rope, v)
        out, lse = fa._flash_forward(q, k, v, causal, None, None, False, **shared)
        return out, lse

    def forward(q, k_nope, k_rope, v, forward=forward):
        out, lse = forward(q, k_nope, k_rope, v)
        return (out.reshape(b, length, -1) if packed else out), lse

    if kind == "fwd":
        return (jax.jit(lambda *a: forward(*a)[0][..., :h * MLA_V if packed
                                                  else MLA_V]),
                (q, k_nope, k_rope, v))
    out, lse = jax.jit(forward)(q, k_nope, k_rope, v)

    def backward(q, k_nope, k_rope, v, out, lse, g):
        k, v, shared = operands(k_nope, k_rope, v)
        if form == "padded":
            g = jnp.pad(g, ((0, 0),) * 3 + ((0, d - MLA_V),))
        if packed and not rows_taken:
            out, g = (x.reshape(b, length, h, -1) for x in (out, g))
        dq, dk, dv, *dks = fa._flash_backward(q, k, v, out, lse, g, causal,
                                              None, None, False, **shared)
        if packed:
            if not rows_taken:
                dk = jnp.concatenate([dk, dv], axis=-1).reshape(b, length, -1)
            return dq, dk, dks[0]
        if form == "shared":
            return dq, dk, dks[0], dv
        split = d - MLA_SHARED
        return (dq, dk[..., :split],
                dk[..., split:].astype(jnp.float32).sum(axis=2).astype(dk.dtype),
                dv[..., :MLA_V])

    return jax.jit(backward), (q, k_nope, k_rope, v, out, lse, g)


def mimo_operands(name, length=None):
    """``(q, k, v, sink or None, g, the call's keywords)`` of one of
    MiMo-V2.5's calls, as ``models/mimo_v2.py`` hands them."""
    import jax
    import jax.numpy as jnp

    b, full_length, h, d, _, _ = SHAPES[name]
    length = length or full_length
    window, kv_heads, has_sink = MIMO_FORMS[MIMO[name]]
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(keys[0], (b, length, h, d), jnp.bfloat16)
    k = jax.random.normal(keys[1], (b, length, kv_heads, d), jnp.bfloat16)
    v = jax.random.normal(keys[2], (b, length, kv_heads * MIMO_V), jnp.bfloat16)
    sink = 2.0 * jax.random.normal(keys[3], (h,), jnp.float32) if has_sink else None
    g = jax.random.normal(keys[4], (b, length, h * MIMO_V), jnp.bfloat16)
    return q, k, v, sink, g, dict(window=window, heads=(h, kv_heads))


def build_mimo(fa, name, blocks=None):
    """One of MiMo-V2.5's calls, forward or backward on a forward's
    residuals. ``blocks``: the backward's ``(bq, bk)`` override, whose q
    tile the forward that makes the residuals is asked for too (the lse
    plane's rows are the forward's q block)."""
    import jax

    q, k, v, sink, g, call = mimo_operands(name)
    sinks = () if sink is None else (sink,)

    def forward(q, k, v, *sinks):
        return fa._flash_forward(q, k, v, True, blocks[0] if blocks else None,
                                 None, False, **call,
                                 sink=sinks[0] if sinks else None)

    if kind_of(name) == "fwd":
        return jax.jit(forward), (q, k, v, *sinks)
    out, lse = jax.jit(forward)(q, k, v, *sinks)
    return (jax.jit(lambda q, k, v, o, lse, g, *sinks: fa._flash_backward(
        q, k, v, o, lse, g, True, None, None, False, **call,
        sink=sinks[0] if sinks else None)), (q, k, v, out, lse, g, *sinks))


def check_mimo(fa, name):
    """The call's result and every gradient (d sink among them) against the
    dot path with the sink as a concatenated column, on the chip at
    ``MIMO_CHECK_LENGTH`` positions: relative L2 distance of each, and whether
    all are within ``MIMO_CHECK_TOLERANCE``."""
    import jax
    import jax.numpy as jnp

    from autodist_tpu.models.mimo_v2 import sink_dot_attention

    q, k, v, sink, g, call = mimo_operands(name, MIMO_CHECK_LENGTH)
    b, length, h, _ = q.shape
    kv_heads = call["heads"][1]
    sinks = () if sink is None else (sink,)

    def run(attend):
        def loss(q, k, v, *sinks):
            out = attend(q, k, v, sinks[0] if sinks else None)
            out = out.reshape(b, length, -1)
            return jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32)), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(3 + len(sinks))), has_aux=True))(
                q, k, v, *sinks)
        return (out, *grads)

    got = run(lambda q, k, v, s: fa.flash_attention(q, k, v, causal=True,
                                                    sink=s, **call))
    with jax.default_matmul_precision("highest"):
        want = run(lambda q, k, v, s: sink_dot_attention(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32).reshape(b, length, kv_heads, -1),
            call["window"], s, jnp.float32))

    def distance(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    parts = ("out", "dq", "dk", "dv", "dsink")[:len(got)]
    record = {"shape": name, "check": True, "length": MIMO_CHECK_LENGTH,
              **{part: distance(a, b) for part, a, b in zip(parts, got, want)}}
    record["agree"] = all(record[part] <= MIMO_CHECK_TOLERANCE for part in parts)
    return record


def _on_heads(fa, b, length, heads, rows):
    """``fa``'s forward and backward for a checkout that takes no rows: the
    operands named in ``rows`` (and with ``v`` the result, its gradient) are
    reshaped to ``[B, L, heads, D]`` on the way in and back on the way out,
    inside the timed call."""
    import types

    def to_heads(x, n):
        return x.reshape(b, length, n, -1) if x.ndim == 3 else x

    def to_rows(x, c):
        return x.reshape(b, length, -1) if c in rows else x

    def forward(q, k, v, *a, **kw):
        out, lse = fa._flash_forward(
            *(to_heads(x, n) for x, n in zip((q, k, v), heads)), *a, **kw)
        return to_rows(out, "v"), lse

    def backward(q, k, v, o, lse, g, *a, **kw):
        grads = fa._flash_backward(
            *(to_heads(x, n) for x, n in zip((q, k, v), heads)),
            to_heads(o, heads[0]), lse, to_heads(g, heads[0]), *a, **kw)
        return tuple(to_rows(x, c) for x, c in zip(grads, "qkv"))

    return types.SimpleNamespace(
        _flash_forward=forward, _flash_backward=backward,
        **{name: getattr(fa, name) for name in (
            "DEFAULT_Q_BLOCK", "_forward_blocks", "_backward_blocks")
           if hasattr(fa, name)})


def build(fa, name, blocks=None):
    import jax
    import jax.numpy as jnp

    if name in MLA:
        return build_mla(fa, name)
    if name in MIMO:
        return build_mimo(fa, name, blocks)
    b, length, h, d, causal, kind = SHAPES[name]
    window, kv_heads = BANDS.get(name, (None, h))
    # a checkout older than the window takes no such argument
    band = {} if window is None else {"window": window}
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(key, (b, length, heads, d), jnp.bfloat16)
               for key, heads in zip(keys, (h, kv_heads, kv_heads)))
    rows = ROWS.get(name, "")
    if rows:
        q, k, v = (x.reshape(b, length, -1) if c in rows else x
                   for c, x in zip("qkv", (q, k, v)))
        if takes_rows(fa):
            band["heads"] = (h, kv_heads)
        else:       # an older checkout: the reshape its models handed it
            fa = _on_heads(fa, b, length, (h, kv_heads, kv_heads), rows)
    if kind == "fwd":
        chooses = hasattr(fa, "_forward_blocks")
        default = None if chooses else fa.DEFAULT_Q_BLOCK
        fn = jax.jit(lambda q, k, v: fa._flash_forward(
            q, k, v, causal, default, default, False, **band))
        args = (q, k, v)
    elif kind in ("bwd", "ring-bwd"):
        ring = kind == "ring-bwd"
        chooses = hasattr(fa, "_backward_blocks") and not ring
        block = None if chooses else fa.DEFAULT_Q_BLOCK
        # the lse plane's rows are the forward's q block: the backward's q tile
        out, lse = jax.jit(lambda q, k, v: fa._flash_forward(
            q, k, v, causal, blocks[0] if blocks else block, block, False,
            **band))(q, k, v)
        g = jax.random.normal(jax.random.PRNGKey(1), out.shape, q.dtype)
        if ring:
            k_offset = length if name == "bwd-ring-diag" else 0
            fn = jax.jit(lambda q, k, v, o, lse, g, q_off, k_off:
                         fa._flash_backward(
                             q, k, v, o, lse, g, causal, block, block, False,
                             q_offset=q_off, k_offset=k_off,
                             out_dtype=jnp.float32))
            args = (q, k, v, out, lse, g, jnp.int32(length), jnp.int32(k_offset))
        else:
            fn = jax.jit(lambda q, k, v, o, lse, g: fa._flash_backward(
                q, k, v, o, lse, g, causal, block, block, False, **band))
            args = (q, k, v, out, lse, g)
    else:
        carry = (jnp.zeros((b, h, length, d), jnp.float32),
                 jnp.full((b, h, length), -1e30, jnp.float32),
                 jnp.zeros((b, h, length), jnp.float32))
        k_offset = length if name == "carry-diag" else 0
        fn = jax.jit(lambda q, k, v, carry, q_off, k_off:
                     fa.flash_attention_with_carry(
                         q, k, v, carry, causal=causal, q_offset=q_off,
                         k_offset=k_off))
        args = (q, k, v, carry, jnp.int32(length), jnp.int32(k_offset))
    return fn, args


def build_head(fx, name, blocks=None, two_kernels=False):
    """The head's forward, or forward and backward (each kernel is read
    from the trace by its own name). ``two_kernels``: the backward as dh and
    dw whatever the rule says of one pass. The rule is read when the
    function is traced, so it stays overridden until the next build."""
    import inspect

    import jax
    import jax.numpy as jnp

    n, d, v, layout, kind = HEAD_SHAPES[name]
    rule = vars(fx).setdefault("_fit_blocks_rule", fx._fit_blocks)
    chooses = "kernel" in inspect.signature(rule).parameters
    if blocks is not None and not chooses:
        raise SystemExit("this checkout's _fit_blocks fits no kernel alone: "
                         "a fixed default")
    which = kind.split("-")[1]

    def fit(kernel, *a, **kw):
        if kernel == "bwd" and (two_kernels or which in ("dh", "dw")):
            return None     # xent-dh / xent-dw time the two kernels' own
        if kernel == which and blocks is not None:
            return blocks
        return rule(kernel, *a, **kw)

    fx._fit_blocks = fit if chooses else rule
    start = (None, None) if chooses else (fx.DEFAULT_N_BLOCK, fx.DEFAULT_V_BLOCK)
    w_vd = layout == "vd"
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    h = jax.random.normal(keys[0], (n, d), jnp.bfloat16)
    w = jax.random.normal(keys[1], (v, d) if w_vd else (d, v), jnp.float32) * 0.02
    b = jnp.zeros((v,), jnp.float32)

    def forward(h, w, b):
        return fx._forward(h, w, b, *start, False, w_vd)

    if kind == "xent-fwd":
        return jax.jit(forward), (h, w, b)
    g = jax.random.normal(keys[2], (n,), jnp.float32) / n
    return (jax.jit(lambda h, w, b, g: fx._backward(
        h, w, b, forward(h, w, b), g, *start, False, w_vd)), (h, w, b, g))


def check_head(fx, name, blocks):
    """dh, dw, db of the backward as the rule runs it against the two
    kernels, on the chip: relative L2 distance of each, and whether all are
    within ``CHECK_TOLERANCE``."""
    import jax
    import jax.numpy as jnp

    from autodist_tpu import telemetry

    fn, args = build_head(fx, name, blocks)
    got = jax.block_until_ready(fn(*args))
    passes = telemetry.snapshot().get("xent.bwd.passes")
    fn, args = build_head(fx, name, two_kernels=True)
    want = jax.block_until_ready(fn(*args))

    def distance(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    record = {"shape": name, "blocks": blocks, "check": True, "passes": passes,
              **{part: distance(a, b)
                 for part, a, b in zip(CHECK_TOLERANCE, got, want)}}
    record["agree"] = all(record[part] <= limit     # a NaN agrees with nothing
                          for part, limit in CHECK_TOLERANCE.items())
    return record


def fill_pct(fa, name, blocks, gauges):
    """Of the (query, key) pairs of the score tiles one (batch, head) of the
    call runs (``tiles_plain`` + ``tiles_masked`` of its gauges, at the tile
    its walk takes), the share the mask keeps, in percent: 50 under the full
    diagonal's tiles at their best, 12.8 under a window of 128 in 512 x 512
    tiles, 49.6 under the walk fitted to it. None for a shape without a
    mask, or a checkout without the gauges."""
    _, length, _, d, causal, kind = SHAPES[name]
    if not causal or kind not in ("fwd", "bwd") or not gauges:
        return None
    window = MIMO_FORMS[MIMO[name]][0] if name in MIMO else \
        BANDS.get(name, (None,))[0]
    band_span = getattr(fa, "_band_span", lambda *a: 0)  # older: tiles alone
    if kind == "fwd":
        bq, bk, sub = blocks or fa._forward_blocks(length, length, d, 2, None, None)
        span = band_span(window, bq, bk, sub)
        tile = 128 * span if span else bq * sub
    else:
        bq, bk = blocks or fa._backward_blocks(length, length, None, None)
        span = band_span(window, bk, length, bq) \
            if gauges.get("flash.bwd.passes") == 1 else 0
        tile = 128 * span if span else bq * bk
    tiles = sum(gauges[f"flash.{kind}.tiles_{c}"] for c in ("plain", "masked"))
    seen = min(window or length, length)        # keys a late query sees
    visible = seen * (seen + 1) // 2 + (length - seen) * seen
    return 100.0 * visible / (tiles * tile)


def measure(modules, name, blocks, calls, two_kernels=False):
    import jax

    kind = kind_of(name)
    if name in HEAD_SHAPES:
        fn, args = build_head(modules["fused_xent"], name, blocks, two_kernels)
    else:
        fa = modules["flash_attention"]
        if blocks is not None:
            chooser = "_backward_blocks" if kind == "bwd" else "_forward_blocks"
            if not hasattr(fa, chooser):
                raise SystemExit(f"this checkout has no {chooser}: a fixed default")
            setattr(fa, chooser, lambda *a, **kw: blocks)
        fn, args = build(fa, name, blocks if kind == "bwd" else None)
    try:
        jax.block_until_ready(fn(*args))
    except Exception as e:  # noqa: BLE001 — a sweep goes on past refused tiles
        if blocks is None:
            raise
        return {"shape": name, "blocks": blocks,
                "refused": str(e).splitlines()[0][:300]}
    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            t0 = time.perf_counter()
            outs = [fn(*args) for _ in range(calls)]
            jax.block_until_ready(outs)
            call = (time.perf_counter() - t0) / calls * 1e3
        parts = {kernel: sorted(kernel_ms(trace_dir, kernel))
                 for kernel in kernels_of(name)}
        device_all = all_ops_ms(trace_dir) / calls if name in AROUND else None
    parts = {kernel: ms for kernel, ms in parts.items() if ms}
    if not parts:
        raise SystemExit(f"{name}: the trace holds no {kernels_of(name)} event")
    record = {"shape": name, "blocks": blocks,
              **({"two_kernels": two_kernels} if kind == "xent-bwd" else {}),
              "events": sum(len(ms) for ms in parts.values()),
              "kernel_ms_median": sum(ms[len(ms) // 2] for ms in parts.values()),
              "kernel_ms_min": sum(ms[0] for ms in parts.values()),
              "call_ms_host": call}
    if device_all is not None:
        record["device_ms_all"] = device_all
    if len(KERNELS[kind]) > 1:      # the one-pass backward holds no flash_bwd_dq
        record["parts"] = {kernel: ms[len(ms) // 2] for kernel, ms in parts.items()}
    from autodist_tpu import telemetry
    prefix = GAUGES.get(kind)
    gauges = {k: v for k, v in telemetry.snapshot().items()
              if prefix and k.startswith(prefix)}
    if gauges:      # a checkout older than the gauges has none
        record["gauges"] = gauges
    if name in SHAPES:
        fill = fill_pct(modules["flash_attention"], name, blocks, gauges)
        if fill is not None:
            record["fill_pct"] = round(fill, 2)
    return record


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="checkout to import autodist_tpu from")
    parser.add_argument("--shapes", default=",".join((*SHAPES, *HEAD_SHAPES)))
    parser.add_argument("--blocks", action="append", default=[],
                        help="the forward's bq,bk,sub override; may repeat")
    parser.add_argument("--bwd-blocks", action="append", default=[],
                        help="the backward's bq,bk override; may repeat")
    parser.add_argument("--xent-blocks", action="append", default=[],
                        help="a head kernel's bn,bv override; may repeat")
    parser.add_argument("--walk-groups", default=None,
                        help="override _WALK_GROUPS, the plain tiles the "
                             "forward's walk takes as one block, largest "
                             "first (e.g. 4,2; 0: none, the one-tile chain)")
    parser.add_argument("--group-q-chunks", type=int, default=None,
                        help="override _GROUP_Q_CHUNKS, the pieces of the "
                             "queries a tile is cut into inside a block")
    parser.add_argument("--resident-dq-bytes", type=int, default=None,
                        help="override _RESIDENT_DQ_BYTES: the float32 dQ of a "
                             "(batch, head) up to which the backward is one pass")
    parser.add_argument("--check", action="store_true",
                        help="xent-bwd* shapes: compare the one pass's dh, dw, "
                             "db with the two kernels' instead of timing them; "
                             "mimo-* shapes: the call and its gradients with "
                             "the dot path's")
    parser.add_argument("--calls", type=int, default=20)
    args = parser.parse_args(argv)

    sys.path.insert(0, os.path.abspath(args.root))
    import jax
    if jax.default_backend() != "tpu":
        raise SystemExit(f"needs the TPU, the backend is {jax.default_backend()!r}")
    modules = {m: importlib.import_module(f"autodist_tpu.ops.{m}")
               for m in ("flash_attention", "fused_xent")}

    if args.resident_dq_bytes is not None:
        modules["flash_attention"]._RESIDENT_DQ_BYTES = args.resident_dq_bytes
    if args.walk_groups is not None:
        modules["flash_attention"]._WALK_GROUPS = tuple(
            int(x) for x in args.walk_groups.split(",") if int(x) > 1)
    if args.group_q_chunks is not None:
        modules["flash_attention"]._GROUP_Q_CHUNKS = args.group_q_chunks

    def plans(flags):
        return [tuple(int(x) for x in b.split(",")) for b in flags] or [None]

    overrides = {"fwd": plans(args.blocks), "bwd": plans(args.bwd_blocks),
                 **dict.fromkeys(("xent-fwd", "xent-dh", "xent-dw", "xent-bwd"),
                                 plans(args.xent_blocks))}
    def emit(record):
        print(json.dumps({"root": args.root, **record}), flush=True)
        return record.get("agree", True)

    agree = True
    for name in args.shapes.split(","):
        kind = kind_of(name)
        for blocks in overrides.get(kind, [None]):
            if args.check and kind == "xent-bwd":
                agree &= emit(check_head(modules["fused_xent"], name, blocks))
            elif args.check and name in MIMO:
                agree &= emit(check_mimo(modules["flash_attention"], name))
            else:
                emit(measure(modules, name, blocks, args.calls))
        if kind == "xent-bwd" and not args.check:   # beside it, the two kernels
            emit(measure(modules, name, None, args.calls, two_kernels=True))
    if not agree:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
