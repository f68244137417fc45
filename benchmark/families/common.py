"""What every model family hands the job: one dataclass, and the seeded
token source the families share."""

import dataclasses
from typing import Any, Callable, Dict, List

import numpy as np


@dataclasses.dataclass
class Built:
    """A configuration under a traffic mix, ready to train."""
    params: Any                        # on the device, from the seed
    loss_fn: Callable                  # the system's loss(params, batch)
    optimizer: Any                     # optax transformation
    pool: List[Dict[str, np.ndarray]]  # host batches the job cycles
    sample: Dict[str, np.ndarray]      # the correctness check's sequences
    tokens_per_step: int               # input positions an optimizer step sees
    train_flops_per_token: float       # required operations, benchmark/flops.py
    kernel_cost_per_step: Any          # flops.KernelCost of the Pallas calls, or None
    reference_config: Dict[str, Any]   # what reference/<family>.py needs


def zipf_tokens(rng: np.random.Generator, shape, vocab_size: int,
                low: int = 0) -> np.ndarray:
    """Token ids in [low, vocab_size) with a Zipf-like law, p(rank) ~
    1/(rank + 10), as text has: a model can learn the unigram law within a
    few steps, so the loss falls where uniform tokens would sit at ln V."""
    ranks = np.arange(vocab_size - low, dtype=np.float64)
    cdf = np.cumsum(1.0 / (ranks + 10.0))
    cdf /= cdf[-1]
    draws = np.searchsorted(cdf, rng.random(size=shape), side="right")
    return (np.minimum(draws, vocab_size - low - 1) + low).astype(np.int32)


def optimizer(config: dict, default: str):
    """The optax transformation the configuration file assumes."""
    import optax
    assumed = config.get("assumed", {})
    return getattr(optax, assumed.get("optimizer", default))(
        assumed.get("learning_rate", 1e-4))
