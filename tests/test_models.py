"""Model zoo: each model trains a few steps under a distribution strategy."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu import AutoDist
from autodist_tpu.models import bert, ncf, resnet, transformer_lm, vgg
from autodist_tpu.strategy import AllReduce, Parallax, PartitionedPS, PS

TINY_LM = transformer_lm.TransformerLMConfig(
    vocab_size=128, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_len=64,
    dtype=jnp.float32)


def test_transformer_lm_trains_allreduce():
    model, params = transformer_lm.init_params(TINY_LM)
    loss_fn = transformer_lm.make_loss_fn(model)
    batch = transformer_lm.synthetic_batch(TINY_LM, batch_size=16, seq_len=16)
    ad = AutoDist(strategy_builder=AllReduce())
    step = ad.function(loss_fn, params, optax.adam(1e-2), example_batch=batch)
    losses = [float(step(batch)) for _ in range(4)]
    assert losses[-1] < losses[0]
    assert np.isfinite(losses).all()


@pytest.mark.parametrize("tied", [False, True])
def test_transformer_lm_fused_head_matches_xla_head(tied):
    """fused_head=True (pallas head+loss) must equal the XLA-head loss and
    produce the same training trajectory, tied and untied."""
    cfg = dataclasses.replace(TINY_LM, tied_output=tied)
    cfg_f = dataclasses.replace(cfg, fused_head=True)
    model, params = transformer_lm.init_params(cfg)
    model_f, _ = transformer_lm.init_params(cfg_f)
    batch = transformer_lm.synthetic_batch(cfg, batch_size=8, seq_len=16)
    l_xla = float(transformer_lm.make_loss_fn(model)(params, batch))
    l_fused = float(transformer_lm.make_loss_fn(model_f)(params, batch))
    np.testing.assert_allclose(l_fused, l_xla, rtol=1e-5)

    def run(m):
        ad = AutoDist(strategy_builder=AllReduce())
        step = ad.function(transformer_lm.make_loss_fn(m), params,
                           optax.adam(1e-2), example_batch=batch)
        return [float(step(batch)) for _ in range(4)]

    np.testing.assert_allclose(run(model_f), run(model), rtol=5e-4, atol=5e-4)


def test_transformer_lm_embedding_detected_sparse_and_parallax_routes_it():
    # Untied output: the embedding is gather-only (like the reference lm1b model's
    # separate softmax weights), so its gradient is row-sparse.
    cfg = dataclasses.replace(TINY_LM, tied_output=False)
    model, params = transformer_lm.init_params(cfg)
    loss_fn = transformer_lm.make_loss_fn(model)
    batch = transformer_lm.synthetic_batch(cfg, batch_size=8, seq_len=16)
    ad = AutoDist(strategy_builder=Parallax())
    step = ad.function(loss_fn, params, optax.sgd(1e-2), example_batch=batch)
    step(batch)
    kinds = {n.var_name: n.WhichOneof("synchronizer") for n in ad._strategy.node_config}
    emb_nodes = [k for n, k in kinds.items() if "embed" in n and "pos" not in n]
    assert emb_nodes and all(k == "ps_synchronizer" for k in emb_nodes)


def test_transformer_lm_remat_matches_no_remat():
    cfg_plain = TINY_LM
    cfg_remat = dataclasses.replace(cfg_plain, remat=True)
    model_p, params = transformer_lm.init_params(cfg_plain)
    model_r, _ = transformer_lm.init_params(cfg_remat)
    batch = transformer_lm.synthetic_batch(cfg_plain, batch_size=8, seq_len=16)
    lp = transformer_lm.make_loss_fn(model_p)(params, batch)
    lr = transformer_lm.make_loss_fn(model_r)(params, batch)
    np.testing.assert_allclose(float(lp), float(lr), rtol=1e-6)


def test_resnet_tiny_trains():
    cfg = resnet.ResNet50Config(num_classes=10, stage_sizes=(1, 1), width=8,
                                dtype=jnp.float32, norm_groups=4)
    model, params = resnet.init_params(cfg, image_size=32)
    loss_fn = resnet.make_loss_fn(model)
    batch = resnet.synthetic_batch(cfg, batch_size=8, image_size=32)
    ad = AutoDist(strategy_builder=PS())
    step = ad.function(loss_fn, params, optax.sgd(0.05), example_batch=batch)
    losses = [float(step(batch)) for _ in range(3)]
    assert losses[-1] < losses[0]


def test_resnet_sync_batchnorm_is_cross_replica():
    """norm='batch' computes GLOBAL batch statistics under the data-sharded
    step: the 8-device AllReduce loss equals the single-process jit loss on
    the same batch (per-replica statistics would differ — each shard of 2
    examples has different moments than the global 16)."""
    cfg = resnet.ResNet50Config(num_classes=10, stage_sizes=(1, 1), width=8,
                                dtype=jnp.float32, norm="batch")
    model, params = resnet.init_params(cfg, image_size=32)
    loss_fn = resnet.make_loss_fn(model)
    batch = resnet.synthetic_batch(cfg, batch_size=16, image_size=32)

    single = float(jax.jit(loss_fn)(params, {k: jnp.asarray(v)
                                             for k, v in batch.items()}))
    ad = AutoDist(strategy_builder=AllReduce())
    step = ad.function(loss_fn, params, optax.sgd(0.05), example_batch=batch)
    # step() returns the loss at the PRE-update params (value_and_grad), so
    # the first call is directly comparable to the single-process loss.
    losses = [float(step(batch)) for _ in range(3)]
    np.testing.assert_allclose(losses[0], single, rtol=1e-5, atol=1e-5)
    # And it trains.
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_resnet_sync_batchnorm_ema_inference_parity():
    """The flag-gated BN-EMA eval mode: stats calibrated from ONE batch equal
    that batch's own moments, so the EMA-reading model reproduces batch-stats
    outputs exactly on it — and, unlike batch-stats mode, gives the same
    per-example logits at ANY eval batch size (reference BatchNorm inference
    behavior; stats live outside params)."""
    import dataclasses

    cfg = resnet.ResNet50Config(num_classes=4, stage_sizes=(1,), width=8,
                                dtype=jnp.float32, norm="batch")
    model, params = resnet.init_params(cfg, image_size=16)
    rng = np.random.RandomState(0)
    images = rng.randn(4, 16, 16, 3).astype(np.float32)

    ema = resnet.calibrate_bn_ema(model, params, [images])
    eval_model = resnet.ResNet(dataclasses.replace(cfg, bn_ema=True))
    y_ema = np.asarray(eval_model.apply({"params": params, "bn_ema": ema},
                                        images))
    y_batch = np.asarray(model.apply({"params": params}, images))
    np.testing.assert_allclose(y_ema, y_batch, rtol=1e-5, atol=1e-5)
    # Batch-size independence: a singleton eval batch scores identically.
    y_one = np.asarray(eval_model.apply({"params": params, "bn_ema": ema},
                                        images[:1]))
    np.testing.assert_allclose(y_one[0], y_ema[0], rtol=1e-5, atol=1e-5)


def test_vgg_tiny_trains_partitioned_ps():
    model = vgg.VGG16(num_classes=10, dtype=jnp.float32)
    images = jnp.zeros((2, 32, 32, 3))
    params = jax.jit(model.init)(jax.random.PRNGKey(0), images)["params"]
    loss_fn = vgg.make_loss_fn(model)
    rng = np.random.RandomState(0)
    batch = {"images": rng.randn(8, 32, 32, 3).astype(np.float32),
             "labels": rng.randint(0, 10, size=(8,)).astype(np.int32)}
    ad = AutoDist(strategy_builder=PartitionedPS())
    step = ad.function(loss_fn, params, optax.sgd(0.01), example_batch=batch)
    losses = [float(step(batch)) for _ in range(3)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_bert_tiny_mlm_trains():
    cfg = bert.BertConfig(vocab_size=128, d_model=32, n_heads=4, n_layers=2,
                          d_ff=64, max_len=64, dtype=jnp.float32)
    model = bert.Bert(cfg)
    batch = bert.synthetic_batch(cfg, batch_size=8, seq_len=16, n_predictions=4)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), jnp.asarray(batch["tokens"]),
                        jnp.asarray(batch["token_types"]))["params"]
    loss_fn = bert.make_mlm_loss_fn(model)
    ad = AutoDist(strategy_builder=AllReduce())
    step = ad.function(loss_fn, params, optax.adam(1e-2), example_batch=batch)
    losses = [float(step(batch)) for _ in range(4)]
    assert losses[-1] < losses[0]


def test_ncf_trains_parallax_sparse():
    cfg = ncf.NeuMFConfig(num_users=64, num_items=32, mf_dim=8, mlp_dims=(16, 8))
    model = ncf.NeuMF(cfg)
    batch = ncf.synthetic_batch(cfg, batch_size=16)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), jnp.asarray(batch["users"]),
                        jnp.asarray(batch["items"]))["params"]
    loss_fn = ncf.make_loss_fn(model)
    ad = AutoDist(strategy_builder=Parallax())
    step = ad.function(loss_fn, params, optax.adam(1e-2), example_batch=batch)
    losses = [float(step(batch)) for _ in range(4)]
    assert losses[-1] < losses[0]
    kinds = {n.var_name: n.WhichOneof("synchronizer") for n in ad._strategy.node_config}
    emb = [k for n, k in kinds.items() if "embed" in n and "embedding" in n.lower()]
    assert emb and all(k == "ps_synchronizer" for k in emb)


def test_densenet_tiny_trains():
    from autodist_tpu.models import densenet
    cfg = densenet.DenseNet121Config(num_classes=10, block_sizes=(2, 2),
                                     growth_rate=8, init_features=16,
                                     dtype=jnp.float32, norm_groups=4)
    model, params = densenet.init_params(cfg, image_size=32)
    loss_fn = densenet.make_loss_fn(model)
    batch = densenet.synthetic_batch(cfg, batch_size=8, image_size=32)
    ad = AutoDist(strategy_builder=AllReduce())
    step = ad.function(loss_fn, params, optax.sgd(0.05), example_batch=batch)
    losses = [float(step(batch)) for _ in range(3)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_inception_v3_tiny_trains():
    from autodist_tpu.models import inception
    # Full-size stem needs 299px; a reduced 96px input and one block per
    # repeated stage still exercise every block type (A, B grid-reduce,
    # C factorized-7x7, D, E) — the full 11-block graph costs ~80s of XLA
    # compile on the CPU test host for no extra coverage.
    cfg = inception.InceptionV3Config(num_classes=10, dtype=jnp.float32,
                                      norm_groups=4, repeats=(1, 1, 1))
    model, params = inception.init_params(cfg, image_size=96)
    loss_fn = inception.make_loss_fn(model)
    batch = inception.synthetic_batch(cfg, batch_size=4, image_size=96)
    ad = AutoDist(strategy_builder=AllReduce())
    # Inception's init produces large early gradients (~55 global norm at this
    # size); SGD at CNN-test rates diverges, Adam converges.
    step = ad.function(loss_fn, params, optax.adam(1e-3), example_batch=batch)
    losses = [float(step(batch)) for _ in range(3)]
    # Random-label fitting at this depth is noisy step-to-step; the training
    # signal asserted is: finite everywhere and an improvement over the start.
    assert np.isfinite(losses).all() and min(losses[1:]) < losses[0]


def test_lstm_lm_sampled_softmax_trains_parallax():
    from autodist_tpu.models import lstm_lm
    cfg = lstm_lm.LSTMLMConfig(vocab_size=256, emb_dim=16, hidden_dim=32,
                               n_layers=2, num_sampled=64, dtype=jnp.float32)
    model, params = lstm_lm.init_params(cfg)
    loss_fn = lstm_lm.make_loss_fn(model)
    batch = lstm_lm.synthetic_batch(cfg, batch_size=8, seq_len=12)
    ad = AutoDist(strategy_builder=Parallax())
    step = ad.function(loss_fn, params, optax.adam(1e-2), example_batch=batch)
    losses = [float(step(batch)) for _ in range(4)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_lstm_lm_sampled_softmax_approximates_full_softmax():
    # With every vocab id in the sampled set and no importance correction,
    # sampled softmax == full softmax (accidental-hit masking removes the
    # duplicated true class).
    from autodist_tpu.models import lstm_lm
    cfg = lstm_lm.LSTMLMConfig(vocab_size=32, emb_dim=8, hidden_dim=16,
                               n_layers=1, num_sampled=32, dtype=jnp.float32,
                               subtract_log_q=False)
    model, params = lstm_lm.init_params(cfg)
    loss_fn = lstm_lm.make_loss_fn(model)
    batch = lstm_lm.synthetic_batch(cfg, batch_size=4, seq_len=8, sampled=False)
    full = float(loss_fn(params, batch))
    batch["neg_ids"] = np.arange(32, dtype=np.int32)
    sampled = float(loss_fn(params, batch))
    np.testing.assert_allclose(sampled, full, rtol=1e-5)


def test_lstm_lm_bf16_sampled_softmax_trains_and_tracks_f32():
    """The accelerator dtype path: finite bf16 training, losses near the f32
    run within bf16 tolerance (the suite otherwise pins f32, which would make
    the bf16 casts dead code under test)."""
    from autodist_tpu.models import lstm_lm

    def run(dtype):
        cfg = lstm_lm.LSTMLMConfig(vocab_size=256, emb_dim=16, hidden_dim=32,
                                   n_layers=2, num_sampled=64, dtype=dtype)
        model, params = lstm_lm.init_params(cfg)
        loss_fn = lstm_lm.make_loss_fn(model)
        batch = lstm_lm.synthetic_batch(cfg, batch_size=8, seq_len=12)
        ad = AutoDist(strategy_builder=Parallax())
        step = ad.function(loss_fn, params, optax.adam(1e-2), example_batch=batch)
        return [float(step(batch)) for _ in range(4)]

    f32, bf16 = run(jnp.float32), run(jnp.bfloat16)
    assert np.isfinite(bf16).all() and bf16[-1] < bf16[0]
    np.testing.assert_allclose(bf16, f32, rtol=0.05)


def test_lstm_lm_fused_full_softmax_matches_plain():
    """The pallas fused full-softmax loss equals the naive full softmax."""
    from autodist_tpu.models import lstm_lm
    cfg = lstm_lm.LSTMLMConfig(vocab_size=96, emb_dim=8, hidden_dim=16,
                               n_layers=1, dtype=jnp.float32)
    model, params = lstm_lm.init_params(cfg)
    batch = lstm_lm.synthetic_batch(cfg, batch_size=4, seq_len=8, sampled=False)
    plain = float(lstm_lm.make_loss_fn(model)(params, batch))
    fused = float(lstm_lm.make_fused_full_softmax_loss_fn(model)(params, batch))
    np.testing.assert_allclose(fused, plain, rtol=1e-5)
    # And it trains.
    ad = AutoDist(strategy_builder=Parallax())
    step = ad.function(lstm_lm.make_fused_full_softmax_loss_fn(model), params,
                       optax.adam(1e-2), example_batch=batch)
    losses = [float(step(batch)) for _ in range(4)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_lstm_lm_log_q_correction_matches_manual():
    # subtract_log_q shifts each logit by -log q(id) under the log-uniform
    # sampler; verify against a hand-computed correction of the uncorrected loss.
    import dataclasses as dc

    from autodist_tpu.models import lstm_lm
    cfg = lstm_lm.LSTMLMConfig(vocab_size=64, emb_dim=8, hidden_dim=16,
                               n_layers=1, num_sampled=16, dtype=jnp.float32)
    model, params = lstm_lm.init_params(cfg)
    batch = lstm_lm.synthetic_batch(cfg, batch_size=2, seq_len=4)
    corrected = float(lstm_lm.make_loss_fn(model)(params, batch))

    plain_model = lstm_lm.LSTMLMWithHead(dc.replace(cfg, subtract_log_q=False))

    def manual(params, batch):
        tokens, neg_ids = batch["tokens"], batch["neg_ids"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        h = np.asarray(plain_model.apply({"params": params}, inputs),
                       dtype=np.float32)
        w = np.asarray(params["softmax_w"])
        b = np.asarray(params["softmax_b"])

        def log_q(ids):
            q = (np.log(ids + 2.0) - np.log(ids + 1.0)) / np.log(cfg.vocab_size + 1)
            return np.log(q)

        true_logit = np.einsum("bth,bth->bt", h, w[targets]) + b[targets] \
            - log_q(targets.astype(np.float64))
        neg = np.einsum("bth,sh->bts", h, w[neg_ids]) + b[neg_ids] \
            - log_q(neg_ids.astype(np.float64))[None, None, :]
        neg = np.where(neg_ids[None, None, :] == targets[..., None], -1e9, neg)
        all_logits = np.concatenate([true_logit[..., None], neg], axis=-1)
        lse = np.log(np.exp(all_logits - all_logits.max(-1, keepdims=True))
                     .sum(-1)) + all_logits.max(-1)
        return float((-true_logit + lse).mean())

    np.testing.assert_allclose(corrected, manual(params, batch), rtol=1e-4)
