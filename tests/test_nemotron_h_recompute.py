"""Nemotron-H (``models/nemotron_h.py``), per-layer recomputation: it changes
no number, the gradient program launches each kept forward kernel once, and a
checkpointed layer keeps the listed values and nothing else. The cases were
``test_nemotron_h.py``'s (which says what the tiny stack is); they are a file
of their own because every one compiles or traces the whole nine-layer stack,
and one file is one ``xdist`` worker's."""

import collections
import dataclasses
import functools
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from autodist_tpu import telemetry  # noqa: E402
from autodist_tpu.models import common, moe, nemotron_h  # noqa: E402
from tests.nemotron_tiny import TINY, stirred  # noqa: E402


@pytest.mark.parametrize("dtype,kernels,rtol,atol", [
    (jnp.float32, False, 1e-5, 1e-7),
    # the kept values are the values a second forward would make: bfloat16
    # through the kernels agrees as float32 does (the tolerance is XLA's, which
    # fuses the two programs differently, not bfloat16's)
    (jnp.bfloat16, True, 1e-5, 1e-7),
], ids=["f32-xla", "bf16-kernels"])
def test_recomputing_every_layer_changes_no_number(dtype, kernels, rtol, atol):
    cfg = nemotron_h.NemotronHConfig(
        dtype=dtype, rows_bound=40, exact_first_layer=kernels,
        **(dict(attention_impl="flash", ssm_impl="pallas") if kernels else {}),
        **TINY)
    model, params = nemotron_h.init_params(cfg, jax.random.PRNGKey(1))
    params = stirred(params)
    batch = {"tokens": jnp.asarray(
        nemotron_h.synthetic_batch(cfg, 2, 24, seed=5)["tokens"])}
    plain = jax.jit(jax.value_and_grad(nemotron_h.make_loss_fn(model)))(
        params, batch)
    again = jax.jit(jax.value_and_grad(nemotron_h.make_loss_fn(
        nemotron_h.NemotronH(dataclasses.replace(cfg, remat=True)))))(params, batch)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=rtol, atol=atol),
        plain, again)
    # and the layer's loads and passes are sown under it as without it
    _, sown = jax.jit(lambda p, tokens: nemotron_h.NemotronH(
        dataclasses.replace(cfg, remat=True)).apply(
            {"params": p}, tokens, return_hidden=True,
            mutable=["intermediates"]))(params, batch["tokens"][:, :-1])
    loads = nemotron_h.sown_loads(sown["intermediates"])
    assert loads.shape == (4, 8) and float(loads.sum()) == 4 * 2 * 24 * 3
    assert moe.sown_passes(sown["intermediates"]).shape == (4,)


def _kernel_calls(jaxpr, counts=None):
    """Pallas calls by kernel name, sub-programs included."""
    counts = collections.Counter() if counts is None else counts
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            counts[str(eqn.params["name"])] += 1
            continue
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (tuple, list)) else [param]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _kernel_calls(sub, counts)
    return counts


@pytest.fixture(scope="module")
def gradient_programs():
    """``{remat: (kernel calls of jax.grad(loss) by name, the remat.* gauges
    its trace left)}`` of the cell's own settings at the tiny widths."""
    found = {}
    for remat in (False, True):
        telemetry.registry().clear()
        cfg = nemotron_h.NemotronHConfig(
            attention_impl="flash", ssm_impl="pallas", remat=remat,
            exact_first_layer=True, rows_bound=40, **TINY)
        model, params = nemotron_h.init_params(cfg, jax.random.PRNGKey(1))
        batch = {"tokens": jnp.asarray(
            nemotron_h.synthetic_batch(cfg, 2, 40, seed=3)["tokens"])}
        program = jax.make_jaxpr(jax.grad(nemotron_h.make_loss_fn(model)))(
            params, batch)
        found[remat] = (_kernel_calls(program.jaxpr),
                        {k: v for k, v in telemetry.snapshot().items()
                         if k.startswith("remat.")})
    return found


@pytest.mark.parametrize("kernel,calls,under_remat", [
    ("flash_fwd", 1, 1), ("flash_bwd_dkv", 1, 1), ("ssd_fwd", 4, 4),
    ("ssd_bwd", 4, 4), ("conv_silu_fwd", 4, 8), ("conv_silu_bwd", 4, 4),
    ("moe_gmm_fwd", 24, 24)])
def test_a_checkpointed_layer_runs_each_kept_forward_kernel_once(
        gradient_programs, kernel, calls, under_remat):
    """Four Mamba-2 layers and one attention layer: under ``remat`` the
    policy keeps what flash's and the scan's forward rules hand their
    backward, so the gradient program launches those forward kernels once a
    layer, as without ``remat`` (a bare ``jax.checkpoint`` launched each
    twice), and pass 0 of the routed share likewise (its two forward products
    ran again: 32); the convolution's output is not on the list and its
    forward kernel runs again."""
    assert gradient_programs[False][0][kernel] == calls
    assert gradient_programs[True][0][kernel] == under_remat


# What a layer of each kind keeps at the tiny widths, 2 sequences of 40: a
# Mamba-2 layer its [z | xBC | dt], the scan's y (whole chunks of 128) and one
# [64, 128] state a chunk and head; an expert layer the shared expert's up
# product, the router's logits and what pass 0 over the 40 rows of the bound
# makes for its transpose (the gathered rows, the up product, its relu and its
# mask, relu2, the down product that the weights' gradient reads, the rows'
# weights and their indices); attention q, k, v, flash's output and its
# log-sum-exp.
KEPT_BY_KIND = {
    # [z | xBC | dt]; the scan's y and its states in the shapes ssd_fwd wrote
    nemotron_h.MAMBA: [((2, 40, 1028), "bfloat16"), ((2, 128, 256), "bfloat16"),
                       ((2, 1, 2, 128, 128), "float32")],
    nemotron_h.EXPERTS: [((2, 40, 40), "bfloat16"), ((80, 8), "float32"),
                         ((40, 64), "bfloat16"), ((40, 24), "bfloat16"),
                         ((40, 24), "bfloat16"), ((40, 24), "bool"),
                         ((40, 24), "bfloat16"), ((40, 64), "bfloat16"),
                         ((40,), "float32"), ((40,), "float32"), ((40,), "bool"),
                         ((40,), "int32"), ((40,), "int32"), ((40, 1), "int32"),
                         ((3,), "int32"), ((2,), "int32"), ((), "int32")],
    nemotron_h.ATTENTION: [((2, 40, 64), "bfloat16"), ((2, 40, 32), "bfloat16"),
                           ((2, 40, 32), "bfloat16"), ((2, 40, 4, 16), "bfloat16"),
                           ((8, 1, 40), "float32")],
}


def _bytes(kept):
    return sum(math.prod(shape) * jnp.dtype(dtype).itemsize for shape, dtype in kept)


def test_the_kept_values_are_booked_and_absent_without_remat(gradient_programs):
    kept = [v for kind in TINY["pattern"] for v in KEPT_BY_KIND[kind]]
    assert gradient_programs[True][1] == {
        "remat.layers": 9, "remat.kept_values": len(kept),
        # layer 0's [z | xBC | dt] is float32 under exact_first_layer
        "remat.kept_bytes": _bytes(kept) + 2 * 40 * 1028 * 2}
    assert gradient_programs[False][1] == {}


@pytest.mark.parametrize("kind", KEPT_BY_KIND, ids=["mamba", "experts", "attention"])
def test_a_checkpointed_layer_keeps_the_listed_values_and_nothing_else(kind):
    """What one layer under the model's policy hands its backward, by JAX's
    own account: its arguments and exactly the listed values, so no mixer's
    last product (``out_proj``'s, ``down``'s: the next layer keeps the sum as
    its own input) and nothing the elementwise rest makes."""
    from jax._src.ad_checkpoint import saved_residuals
    telemetry.registry().clear()
    cfg = nemotron_h.NemotronHConfig(attention_impl="flash", ssm_impl="pallas",
                                     rows_bound=40, **TINY)
    block = nemotron_h.NemotronHBlock(cfg, kind)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, cfg.d_model))
    params = block.init(jax.random.PRNGKey(1), x)["params"]

    @functools.partial(jax.checkpoint, policy=common.keeping(nemotron_h.KEPT))
    def layer(params, x):
        y, term = block.apply({"params": params}, x)
        return jnp.sum(jnp.square(y)) + term

    made = [(aval.shape, str(aval.dtype))
            for aval, how in saved_residuals(layer, params, x)
            if "from the argument" not in how]
    assert sorted(made) == sorted(KEPT_BY_KIND[kind])
    assert telemetry.gauge("remat.kept_values").value == len(made)
    assert telemetry.gauge("remat.kept_bytes").value == _bytes(made)
