"""The decoder shell of the dropless mixture families (``models/olmoe.py``,
``afmoe.py``, ``lfm2_moe.py``, ``nemotron_h.py``, ``deepseek_v3.py``): the
stack, the loss and the init, written once. A family file holds what is the
family's (its config class, its mixers, its block and the names its
checkpointed layers keep); a property of *the stack* (per-layer
recomputation, a tied head, the fused head's branch) lives here alone.
"""

from typing import Any, Callable, ClassVar, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from autodist_tpu import telemetry
from autodist_tpu.models.common import (RMSNorm, fused_lm_head_nll, jit_init,
                                        keeping)
from autodist_tpu.models.moe import _INIT, _dense

_MODEL_OF = {}      # a family's config class -> its Decoder


class Decoder(nn.Module):
    """``tokens [B, L] -> (logits or hidden, the blocks' second outputs
    summed)``: embed (times ``sqrt(d_model)`` under ``config.mup_enabled``),
    ``block_{i}`` a layer over the float32 residual stream, the final norm,
    and the head or, under ``return_hidden``, the normed rows (the fused-head
    loss owns the projection; the head's parameters exist from init, which
    runs the projecting path). A family subclasses it with ``config: <its
    config class>`` (by which :func:`init_params` finds a config's model) and:

    * ``block``: its block's class, built ``block(config, *arguments)`` with
      each layer's arguments from :meth:`layers`, mapping ``x`` to ``(x, a
      float32 term)``: the share's bias term (``models/moe.py``
      ``RoutedShare``; zero on a dense layer) or OLMoE's router losses;
    * ``final_norm``: the last norm's parameter name;
    * ``tied``: the head is the embedding table (else an untied ``lm_head``);
    * ``kept``: under ``config.remat`` every layer is a ``jax.checkpoint``
      that keeps the values of these names for its backward and makes the
      rest again (gauge ``remat.layers``; ``keeping`` books what is kept).
      Init runs the plain layers: shapes are all it needs.

    What a family's config may say of the stack (absent: as the families
    before it): ``init`` (the embedding's and the head's initializer),
    ``norm_unit_offset`` (the final norm's weight is ``1 + scale``),
    ``n_pred_heads`` (the untied head is that many heads of ``vocab_size``
    side by side, head ``j`` the columns ``[vocab_size * j, vocab_size * (j
    + 1))``, scored by :func:`make_loss_fn` against the token ``j + 1``
    ahead) and ``logits_dtype`` (what the head's product is made in)."""
    config: Any

    block: ClassVar[Any] = None
    final_norm: ClassVar[str] = "ln_f"
    tied: ClassVar[bool] = False
    kept: ClassVar[Tuple[str, ...]] = ()

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        config_class = cls.__dict__.get("__annotations__", {}).get("config")
        if config_class is not None:
            _MODEL_OF[config_class] = cls

    def layers(self):
        """Each layer's arguments to ``block`` after the config."""
        return [()] * self.config.n_layers

    def loss(self, nll, second):
        """The training loss from the mean next-token NLL and the stack's
        second output: their sum, the bias term being zero in value."""
        return nll + second

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False):
        cfg = self.config
        init = getattr(cfg, "init", _INIT)
        embed = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=jnp.float32,
                         param_dtype=jnp.float32, embedding_init=init,
                         name="embed")
        x = embed(tokens)
        if getattr(cfg, "mup_enabled", False):
            x = x * np.float32(cfg.d_model ** 0.5)
        block = self.block
        if getattr(cfg, "remat", False) and not self.is_initializing():
            block = nn.remat(block, policy=keeping(self.kept))
            telemetry.gauge("remat.layers").set(cfg.n_layers)
        second = None
        for i, arguments in enumerate(self.layers()):
            x, term = block(cfg, *arguments, name=f"block_{i}")(x)
            if second is None:   # from zero, as each family's own sum began
                second = jax.tree_util.tree_map(jnp.zeros_like, term)
            second = jax.tree_util.tree_map(jnp.add, second, term)
        x = RMSNorm(cfg.rms_eps, cfg.dtype,
                    getattr(cfg, "norm_unit_offset", False),
                    name=self.final_norm)(x)
        if return_hidden:
            return x, second
        if self.tied:
            # in the sublayers' dtype, as the untied heads compute
            return x @ embed.embedding.astype(cfg.dtype).T, second
        return _dense(cfg.vocab_size * getattr(cfg, "n_pred_heads", 1),
                      getattr(cfg, "logits_dtype", cfg.dtype), "lm_head",
                      init)(x), second


def ahead_nll(logits, tokens, n_heads: int):
    """The mean over ``n_heads`` prediction heads of each head's mean
    cross-entropy over its own valid positions: ``logits [B, L, n_heads *
    V]`` (head ``j`` the columns ``[V j, V (j + 1))``), ``tokens [B, L + 1]``;
    head ``j`` at position ``t`` is scored against ``tokens[t + 1 + j]`` where
    the batch has one, ``L - j`` positions a sequence."""
    b, length, width = logits.shape
    logprobs = jax.nn.log_softmax(
        logits.astype(jnp.float32).reshape(b, length, n_heads, width // n_heads),
        axis=-1)
    at = jnp.arange(length)[:, None] + 1 + jnp.arange(n_heads)[None, :]
    valid = at <= length                                         # [L, heads]
    targets = tokens[:, jnp.minimum(at, length)]                 # [B, L, heads]
    nll = -jnp.take_along_axis(logprobs, targets[..., None], axis=-1)[..., 0]
    per_head = jnp.sum(jnp.where(valid, nll, 0.0), axis=(0, 1)) \
        / (b * jnp.sum(valid, axis=0))
    return per_head.mean()


def make_loss_fn(model: Decoder) -> Callable:
    """Mean next-token cross-entropy + the family's term of the stack's
    second output (``model.loss``: the expert layers' bias terms, zero in
    value, or OLMoE's weighted router losses); batch = ``{"tokens": int32
    [B, L+1]}``. Under ``config.fused_head`` the head and the loss are one
    kernel (``ops/fused_xent``). A family of ``n_pred_heads`` heads is
    scored by :func:`ahead_nll` (gauge ``loss.pred_heads``)."""
    cfg = model.config
    n_pred = getattr(cfg, "n_pred_heads", 1)
    if n_pred > 1 and (cfg.fused_head or model.tied):
        raise ValueError("several prediction heads need the untied XLA head")

    def loss_fn(params, batch):
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        telemetry.gauge("loss.pred_heads").set(n_pred)
        if n_pred > 1:
            logits, second = model.apply({"params": params}, inputs)
            return model.loss(ahead_nll(logits, tokens, n_pred), second)
        if cfg.fused_head:
            h, second = model.apply({"params": params}, inputs,
                                    return_hidden=True)
            nll = fused_lm_head_nll(h, params, targets, tied=model.tied)
        else:
            logits, second = model.apply({"params": params}, inputs)
            logprobs = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            nll = -jnp.take_along_axis(logprobs, targets[..., None],
                                       axis=-1)[..., 0]
        return model.loss(nll.mean(), second)

    return loss_fn


def init_params(config, rng: Optional[jax.Array] = None, batch_size: int = 2):
    """``(the config's family's model, its parameters)``."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    model = _MODEL_OF[type(config)](config)
    tokens = jnp.zeros((batch_size, min(8, config.max_len)), jnp.int32)
    return model, jit_init(model, tokens, rng=rng)
