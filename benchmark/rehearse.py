"""Compile a cell's step at its real size for a chip that is described and
not attached, before any chip time.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py <cell>[:micro_batch[:accumulation]] ...

The TPU's compiler is installed with JAX and compiles for a ``v5e:2x2``
topology without one: it refuses what the chip's compiler would refuse (a
kernel Mosaic cannot lower, a step that does not fit 16 GB) and says how
much memory the step needs (``memory_analysis()``). Nothing runs, so this
says nothing about results or times, and is never reported as a chip run.

The program's runner builds its mesh from ``jax.devices()`` and places its
own parameters, so this file hands it the described devices and shapes:
parameters from ``jax.eval_shape``, the mesh over the topology's devices,
and the runner's own abstract state and batch layout with their shardings
attached. The kernels ask ``jax.default_backend()`` whether to interpret;
the caller steers that (``steer_kernels_to_compile``), not an option of the
program.
"""

import contextlib
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmark import harness  # noqa: E402

TOPOLOGY = "v5e:2x2"


def describe_topology():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu", topology_name=TOPOLOGY)


@contextlib.contextmanager
def steer_kernels_to_compile():
    """The backend is the CPU, so the Pallas kernels would pick interpret
    mode; make them compile. A compile for a described chip can be written
    to the persistent cache but not read back, so the cache is off."""
    import importlib

    import jax
    from jax.experimental.compilation_cache import compilation_cache
    modules = [importlib.import_module("autodist_tpu.ops.flash_attention"),
               importlib.import_module("autodist_tpu.ops.fused_xent")]
    saved = [m._use_interpret for m in modules]
    was_enabled = jax.config.jax_enable_compilation_cache
    for m in modules:
        m._use_interpret = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        for m, fn in zip(modules, saved):
            m._use_interpret = fn
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        compilation_cache.reset_cache()


def compile_cell(cell, devices, micro_batch=None, accumulation=None) -> dict:
    """Facts of the compiled step of ``cell`` on the first ``cell.chips`` of
    ``devices``. ``micro_batch`` and ``accumulation`` override the traffic
    file's, to find the largest micro-batch that fits."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from autodist_tpu import ResourceSpec, strategy as strategies
    from autodist_tpu.model_spec import ModelSpec
    from autodist_tpu.parallel.mesh import build_mesh
    from autodist_tpu.parallel.plan import ShardingPlan
    from autodist_tpu.runner import DistributedRunner, MicroBatched

    traffic, chips = dict(cell.traffic), cell.chips
    if micro_batch is not None:
        traffic["micro_batch"] = micro_batch
    if accumulation is not None:
        traffic["accumulation"] = accumulation
    accum = traffic["accumulation"]
    global_batch = traffic["micro_batch"] * accum * chips
    family = cell.load_module("families", cell.config["family"])
    built = family.build(cell.config, dict(traffic, pool_batches=1), 0,
                         global_batch, abstract=True)
    batch = built.pool[0]
    spec = ResourceSpec(resource_info={
        "nodes": [{"address": "localhost", "tpus": chips, "chief": True}],
        "mesh": traffic["mesh"]})
    model_spec = ModelSpec.from_loss_fn(built.loss_fn, built.params, batch)
    strategy = getattr(strategies, traffic["strategy"])().build(model_spec, spec)
    mesh = build_mesh(axes=traffic["mesh"], devices=list(devices)[:chips])
    runner = DistributedRunner(
        strategy, model_spec, built.loss_fn, built.optimizer, mesh=mesh,
        plan=ShardingPlan.from_strategy(strategy, model_spec),
        accumulation_steps=accum)
    state = runner._abstract_state(built.params)
    runner._ensure_state_shardings(state)
    state = jax.tree_util.tree_map(
        lambda leaf, sharding: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                                    sharding=sharding),
        state, runner._state_shardings)

    def place(leaf):
        micro = isinstance(leaf, MicroBatched)
        struct = leaf.value if micro else leaf
        spec_ = runner.plan.batch_pspec(struct.ndim - (1 if micro else 0))
        spec_ = P(None, *spec_) if micro else spec_
        struct = jax.ShapeDtypeStruct(struct.shape, struct.dtype,
                                      sharding=NamedSharding(mesh, spec_))
        return MicroBatched(struct) if micro else struct

    abstract_batch = jax.tree_util.tree_map(
        place, runner._abstract_batch(batch),
        is_leaf=lambda x: isinstance(x, MicroBatched))
    with mesh:
        compiled = runner._build_step(None).lower(state, abstract_batch).compile()
    facts = harness.compiled_facts(compiled)
    gib = 2.0 ** 30
    return {
        "cell": cell.name, "chips": chips, "micro_batch": traffic["micro_batch"],
        "accumulation": accum, "tokens_per_step": built.tokens_per_step,
        "step_gib": harness.step_bytes(facts["compiled_bytes"]) / gib,
        "temp_gib": facts["compiled_bytes"]["temp"] / gib,
        "tpu_custom_call": facts["tpu_custom_call"],
        "collectives": facts["collectives"],
        "parameters": int(sum(np.prod(x.shape) for x in
                              jax.tree_util.tree_leaves(built.params))),
    }


def main(argv) -> int:
    import json
    topo = describe_topology()
    with steer_kernels_to_compile():
        for arg in argv:
            name, *sizes = arg.split(":")      # <cell>[:micro_batch[:accumulation]]
            try:
                facts = compile_cell(harness.load_cell(name), topo.devices,
                                     *(int(x) for x in sizes))
            except Exception as e:  # noqa: BLE001 — report what the compiler refused
                facts = {"cell": arg, "refused": f"{type(e).__name__}: {str(e)[:600]}"}
            print(json.dumps(facts), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
