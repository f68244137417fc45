"""Cross-process sequence parallelism (driver in test_multiprocess.py).

Long-context is first-class (SURVEY.md §5.7): this script runs the ring-
attention sequence-parallel session over a REAL 2-process mesh — a 4-way
``seq`` axis spanning the process boundary, so the ring's K/V ``ppermute``
hops cross between OS processes (the gloo wire on CPU, ICI/DCN on a pod).
Same protocol as the strategy matrix: the chief runs this script, the
Coordinator re-executes it as the worker, and ``AUTODIST_MATRIX_SINGLE=1``
produces the single-process 4-device reference the 2-process run must match
value-exactly (identical global mesh => identical shard count and rounding).

The chief writes per-step losses + final params to argv[1].
"""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

from autodist_tpu import AutoDist  # noqa: E402
from autodist_tpu.models import transformer_lm  # noqa: E402
from autodist_tpu.parallel.sequence import (  # noqa: E402
    create_sequence_parallel_session)
from autodist_tpu.resource_spec import ResourceSpec  # noqa: E402
from autodist_tpu.strategy import SequenceParallel  # noqa: E402

SEQ = 32
BATCH = 4
STEPS = 3

SINGLE = os.environ.get("AUTODIST_MATRIX_SINGLE") == "1"


def _spec():
    if SINGLE:
        nodes = [{"address": "localhost", "tpus": 4, "chief": True}]
    else:
        nodes = [{"address": "localhost", "tpus": 2, "chief": True},
                 {"address": "127.0.0.1", "tpus": 2}]
    return ResourceSpec(resource_info={"nodes": nodes})


def main(out_path: str):
    cfg = transformer_lm.TransformerLMConfig(
        vocab_size=128, d_model=16, n_heads=2, n_layers=2, d_ff=32,
        max_len=SEQ, dtype=jnp.float32, tied_output=False,
        attention_impl="ring")
    # Multi-host constraint: jax.distributed must bootstrap before the first
    # backend touch, but the session needs the model's parameter SHAPES.
    # jax.eval_shape is backend-free, so abstract params drive the strategy
    # build and real params materialize only after the session (and therefore
    # the multihost init) exists.
    model = transformer_lm.TransformerLM(cfg)
    abstract_params = jax.eval_shape(
        lambda k, t: model.init(k, t)["params"],
        jax.ShapeDtypeStruct((2,), jnp.uint32),
        jax.ShapeDtypeStruct((1, SEQ), jnp.int32))

    ad = AutoDist(_spec(), SequenceParallel(seq_axis_size=4))
    runner = create_sequence_parallel_session(ad, model, abstract_params,
                                              optax.adam(1e-2))
    if not SINGLE:
        assert jax.process_count() == 2, f"process_count={jax.process_count()}"
    assert jax.device_count() == 4
    assert dict(runner.mesh.shape)["seq"] == 4  # spans the process boundary

    _, params = transformer_lm.init_params(cfg)
    state = runner.init(params)
    losses = []
    for step in range(STEPS):
        batch = transformer_lm.synthetic_batch(cfg, batch_size=BATCH,
                                               seq_len=SEQ, seed=step)
        state, loss = runner.run(state, batch)
        losses.append(float(loss))

    if jax.process_index() == 0:
        logical = jax.device_get(runner.logical_params(state))
        flat = {jax.tree_util.keystr(p): np.asarray(l).ravel()[:8].tolist()
                for p, l in jax.tree_util.tree_flatten_with_path(logical)[0]}
        result = {
            "losses": losses,
            "params_sample": flat,
            "process_count": jax.process_count(),
            "device_count": jax.device_count(),
            "mesh": {k: int(v) for k, v in dict(runner.mesh.shape).items()},
        }
        with open(out_path, "w") as f:
            json.dump(result, f)


def start_single_reference(out_path: str, workdir: str):
    """Start this script once, single-process, on a 4-device sim mesh (the
    strategy matrix's shared env recipe, ``tests/mp_env.py``);
    ``mp_env.alongside`` waits for it."""
    from tests.mp_env import start_single_reference as start
    return start([os.path.abspath(__file__), out_path], workdir, device_count=4)


if __name__ == "__main__":
    main(sys.argv[1])
