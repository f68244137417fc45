"""autodist_tpu — a TPU-native distributed training framework.

A strategy-compiled engine in the spirit of AutoDist (reference:
``autodist/autodist.py``, ``docs/design/architecture.rst:27-39``): the user writes
single-device model code; a per-variable distribution **Strategy** (PS, load-balanced PS,
partitioned PS, AllReduce, partitioned/random-axis AllReduce, Parallax hybrid) is built
from the model plus a YAML **resource spec**, and materialized by a backend. Here the
backend is idiomatic JAX/XLA: ``pjit``/``shard_map`` shardings and
``psum``/``reduce_scatter``/``all_gather`` collectives over a TPU mesh (ICI/DCN), instead
of TensorFlow graph rewriting over grpc/NCCL.

Layer map (mirrors reference SURVEY.md §1, re-targeted):

- User API:        :mod:`autodist_tpu.autodist`  (``AutoDist(...).scope()`` / ``function()``)
- Strategy:        :mod:`autodist_tpu.strategy`  (8 builders -> Strategy proto -> compiler)
- IR:              :mod:`autodist_tpu.model_spec` (param-pytree metadata; replaces GraphItem)
- Kernel backend:  :mod:`autodist_tpu.parallel`  (sharding compiler, synchronizers, mesh)
- Runtime:         :mod:`autodist_tpu.runner`    (DistributedRunner; replaces WrappedSession)
- Cluster:         :mod:`autodist_tpu.cluster`, :mod:`autodist_tpu.coordinator`
- Checkpoint:      :mod:`autodist_tpu.checkpoint`
"""

from autodist_tpu.version import __version__

# Typo'd flags (a misspelled AUTODIST_PS_OVERLAP etc.) silently no-op; warn
# at import so they surface at startup instead of in a perf investigation.
from autodist_tpu.const import warn_unknown_autodist_flags as _warn_flags

_warn_flags()

__all__ = ["AutoDist", "get_default_autodist", "ResourceSpec", "train",
           "__version__"]


_LAZY = {"AutoDist": "autodist", "get_default_autodist": "autodist",
         "ResourceSpec": "resource_spec", "train": "training"}


def __getattr__(name):  # PEP 562 lazy imports to keep `import autodist_tpu` light
    if name not in _LAZY:
        raise AttributeError(f"module 'autodist_tpu' has no attribute {name!r}")
    import importlib
    import sys
    import time
    module_name = f"autodist_tpu.{_LAZY[name]}"
    t0 = None if module_name in sys.modules else time.perf_counter()
    module = importlib.import_module(module_name)
    if t0 is not None:
        # The heavy imports (jax, flax, optax behind `autodist`) happen here:
        # their seconds go to the set-up ledger.
        from autodist_tpu import telemetry
        with telemetry.phase("setup.import_s", since=t0):
            pass
    return getattr(module, name)
