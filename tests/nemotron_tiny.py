"""The tiny Nemotron-H stack that ``test_nemotron_h.py`` and
``test_nemotron_h_recompute.py`` build their cases on: the widths, and the
parameters with the constant leaves drawn."""

import jax

# The cell's pattern; 2 heads a group and 2 query heads a KV head; the share:
# experts 2-4 of 8, top-3; a state and a group's heads x head_dim of 128 lanes
# and chunks of 128 for the scan's kernels.
TINY = dict(vocab_size=256, d_model=64, pattern="MEMEM*EME", mamba_heads=4,
            mamba_head_dim=64, n_groups=2, d_state=128, conv_kernel=4, chunk=128,
            n_heads=4, n_kv_heads=2, head_dim=16, d_expert=24, d_shared=40,
            n_experts_routed=8, experts_held=3, first_expert_held=2, top_k=3,
            max_len=64)


def stirred(params, scale=0.2):
    """The leaves that init sets to constants (zeros, ones, a ramp), drawn:
    an ``expert_bias`` large enough to change choices, a ``D``, a norm weight
    and a convolution bias that a dropped factor would show in."""
    def draw(path, x):
        if path[-1].key not in ("expert_bias", "D", "A_log", "norm", "scale",
                                "conv_bias"):
            return x
        key = jax.random.PRNGKey(sum(map(ord, jax.tree_util.keystr(path))))
        return x + scale * jax.random.normal(key, x.shape)
    return jax.tree_util.tree_map_with_path(draw, params)
