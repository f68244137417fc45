"""Multi-host bootstrap: jax.distributed initialization from the coordinator env.

Replaces the reference's per-node ``tf.Server`` startup (``utils/server_starter.py:
48-75``): instead of a grpc server per node, every host joins one SPMD program via
``jax.distributed.initialize`` pointed at the chief's coordination service. The env
variables are set by the Coordinator on workers; the chief derives its own values
from the cluster spec.
"""

from typing import Optional

from autodist_tpu import const
from autodist_tpu.utils import logging

_initialized = False


def maybe_initialize_multihost(cluster=None) -> bool:
    """Initialize jax.distributed when a multi-process env is configured.

    Returns True if distributed init ran (or already had). Single-process runs
    (no coordinator env, single-node spec) skip initialization entirely.
    """
    global _initialized
    if _initialized:
        return True

    coordinator = const.ENV.AUTODIST_COORDINATOR_ADDR.val
    num_processes = const.ENV.AUTODIST_NUM_PROCESSES.val
    process_id = const.ENV.AUTODIST_PROCESS_ID.val

    if not coordinator and cluster is not None and cluster.num_processes > 1:
        # Chief in a multi-node spec: derive from the cluster spec.
        coordinator = cluster.cluster_spec["coordinator"]
        num_processes = cluster.num_processes
        process_id = 0

    if not coordinator or num_processes <= 1:
        return False

    import jax
    if _externally_initialized():
        logging.info("jax.distributed already initialized outside AutoDist; reusing")
        _initialized = True
        return True
    _enable_cpu_collectives(jax)
    logging.info("jax.distributed.initialize(%s, num_processes=%d, process_id=%d)",
                 coordinator, num_processes, process_id)
    try:
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_processes,
                                   process_id=process_id)
    except RuntimeError as e:
        if "must be called before" in str(e):
            raise RuntimeError(
                "Multi-node AutoDist must bootstrap jax.distributed before any "
                "JAX computation, but this process already initialized the XLA "
                "backend (e.g. via jnp array creation or jax.devices()). Keep "
                "model setup in numpy until create_distributed_session(), or "
                "call jax.distributed.initialize() yourself at program start."
            ) from e
        raise
    _initialized = True
    return True


def _enable_cpu_collectives(jax) -> None:
    """Multiprocess SPMD on the CPU backend needs a cross-process collectives
    implementation; with ``none`` every cross-process program fails with
    "Multiprocess computations aren't implemented on the CPU backend". Select
    gloo (bundled with jaxlib) before the backend initializes; a user's
    explicit choice (mpi) is left alone."""
    if jax.config.jax_cpu_collectives_implementation == "none":
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        logging.info("CPU backend: enabled gloo cross-process collectives")


def _externally_initialized() -> bool:
    """True when the user already ran jax.distributed.initialize themselves (the
    standard pattern at the top of pod scripts) — calling it twice raises."""
    try:
        from jax._src import distributed
        return distributed.global_state.client is not None
    except Exception:
        return False
