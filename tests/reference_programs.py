"""The plain references' compiled programs, shared by the cases that compare
against them.

``benchmark/reference/<family>.py`` is float32 whatever the system's dtype,
kernels or recomputation, so the cases of a family's
``test_loss_and_gradients_match_the_plain_reference`` (and whoever else
compares against the reference at the same shapes) compile ONE program
between them: ``jax.jit`` keeps a compiled program by the function object, so
the function is made once a (family, settings).
"""

import functools
import importlib

import jax


@functools.lru_cache(maxsize=None)
def _program(family: str, settings: tuple):
    reference = importlib.import_module(f"benchmark.reference.{family}")
    return jax.jit(jax.value_and_grad(
        lambda params, batch: reference.loss(params, batch, **dict(settings))))


def value_and_grad(family: str, **settings):
    """``jax.jit(jax.value_and_grad(reference.loss))`` of ``(params, batch)``
    under the reference's keyword ``settings`` (hashable values)."""
    return _program(family, tuple(sorted(settings.items())))
