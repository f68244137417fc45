"""MLM pretrain pipeline: corpus prep, dynamic masking, disk-fed BERT training.

Parity target: the reference BERT benchmark consumed pre-masked pretrain
tfrecords (``examples/benchmark/bert.py:82-98`` ->
``utils/input_pipeline.py::create_pretrain_dataset``). Here masking is dynamic
(drawn per batch, deterministic under a seed) over raw token shards — these
tests pin the prep layout, the 80/10/10 recipe, determinism, and an
end-to-end BERT train step from disk.
"""

import os

import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu.data import DataLoader, mlm
from autodist_tpu.data.text_corpus import Vocabulary


def _write_corpus(path, n_words=4000, vocab=40, seed=0):
    rng = np.random.RandomState(seed)
    words = [f"w{i}" for i in range(vocab)]
    with open(path, "w") as f:
        for _ in range(n_words // 10):
            f.write(" ".join(words[rng.randint(0, vocab)] for _ in range(10)))
            f.write("\n")
    return words


def _prep(tmp_path, seq_len=16, segments=False, n_words=4000):
    corpus = str(tmp_path / "corpus.txt")
    words = _write_corpus(corpus, n_words=n_words)
    vocab = Vocabulary(words, oov_buckets=1)
    out = str(tmp_path / "mlm")
    paths = mlm.prepare_mlm_shards(corpus, vocab, out, seq_len=seq_len,
                                   rows_per_shard=64, segments=segments)
    return out, paths, vocab


def test_prep_layout_single_segment(tmp_path):
    out, paths, vocab = _prep(tmp_path, seq_len=16)
    meta = mlm.read_meta(out)
    assert meta["vocab_size"] == mlm.N_SPECIAL + vocab.vocab_size
    assert meta["seq_len"] == 16 and not meta["segments"]
    toks = np.load(paths["tokens"][0])
    typs = np.load(paths["token_types"][0])
    assert toks.shape[1] == 16 and toks.dtype == np.int32
    # Row layout: [CLS] 14 words [SEP]; full rows, no padding.
    assert (toks[:, 0] == mlm.CLS_ID).all()
    assert (toks[:, -1] == mlm.SEP_ID).all()
    body = toks[:, 1:-1]
    assert (body >= mlm.N_SPECIAL).all()
    assert (toks < meta["vocab_size"]).all()
    assert (typs == 0).all()
    # Rows count matches the word budget: n_words // 14 full rows.
    assert meta["rows"] == sum(len(np.load(p)) for p in paths["tokens"])


def test_prep_layout_segment_pairs(tmp_path):
    out, paths, _ = _prep(tmp_path, seq_len=16, segments=True)
    toks = np.load(paths["tokens"][0])
    typs = np.load(paths["token_types"][0])
    for row, typ in zip(toks[:20], typs[:20]):
        assert row[0] == mlm.CLS_ID and row[-1] == mlm.SEP_ID
        (seps,) = np.where(row == mlm.SEP_ID)
        assert len(seps) == 2  # mid + final
        mid = seps[0]
        # types: 0 through the first SEP, 1 after it.
        assert (typ[:mid + 1] == 0).all() and (typ[mid + 1:] == 1).all()
        # both segments non-empty
        assert mid >= 2 and mid <= len(row) - 3


def test_mask_batch_recipe():
    rng = np.random.Generator(np.random.PCG64(0))
    L, B, P = 64, 512, 10
    vocab_size = 100
    tokens = np.full((B, L), mlm.CLS_ID, np.int32)
    tokens[:, 1:-1] = np.random.RandomState(1).randint(
        mlm.N_SPECIAL, vocab_size, (B, L - 2))
    tokens[:, -1] = mlm.SEP_ID
    out = mlm.mask_batch(tokens, rng, vocab_size=vocab_size, max_predictions=P)

    assert out["tokens"].shape == (B, L)
    assert out["mlm_positions"].shape == (B, P)
    live = out["mlm_weights"] > 0
    # 15% of 62 maskable ~ 9.3 -> min(P, 9) = 9 live slots per row.
    assert live.sum(axis=1).min() >= 8 and live.sum(axis=1).max() <= P
    rows = np.arange(B)[:, None]
    # No special position is ever masked.
    assert (out["mlm_positions"][live] != 0).all()
    assert (tokens[rows, out["mlm_positions"]][live] >= mlm.N_SPECIAL).all()
    # Targets are the ORIGINAL tokens at the chosen positions.
    np.testing.assert_array_equal(out["mlm_targets"],
                                  tokens[rows, out["mlm_positions"]])
    # Off-position tokens are untouched.
    untouched = np.ones((B, L), bool)
    untouched[rows, out["mlm_positions"]] = False
    np.testing.assert_array_equal(out["tokens"][untouched], tokens[untouched])
    # 80/10/10 over the live slots (binomial bounds, ~4.6k draws).
    vals = out["tokens"][rows, out["mlm_positions"]][live]
    orig = out["mlm_targets"][live]
    frac_mask = (vals == mlm.MASK_ID).mean()
    frac_keep = (vals == orig).mean()
    assert 0.75 < frac_mask < 0.85, frac_mask
    assert 0.06 < frac_keep < 0.15, frac_keep


def test_masking_is_deterministic_and_fresh_per_batch(tmp_path):
    out, paths, _ = _prep(tmp_path)
    meta = mlm.read_meta(out)

    def stream(n):
        loader = DataLoader(files=paths, batch_size=8, shuffle=True, seed=3,
                            native=False)
        b = mlm.MLMBatcher(loader, vocab_size=meta["vocab_size"],
                           max_predictions=4, seed=11)
        return [b.next() for _ in range(n)]

    a, b = stream(5), stream(5)
    for x, y in zip(a, b):
        for key in x:
            np.testing.assert_array_equal(x[key], y[key])
    # Dynamic masking: successive epochs over the same rows draw different
    # masks (the RoBERTa property static tfrecord masking lacks).
    assert not np.array_equal(a[0]["mlm_positions"], a[1]["mlm_positions"])


def test_bert_trains_from_disk(tmp_path):
    from autodist_tpu import AutoDist
    from autodist_tpu.models import bert
    from autodist_tpu.models.common import jit_init
    from autodist_tpu.strategy import AllReduce

    out, paths, _ = _prep(tmp_path, seq_len=16, n_words=8000)
    meta = mlm.read_meta(out)
    cfg = bert.BertConfig(vocab_size=meta["vocab_size"], d_model=32, n_heads=2,
                          n_layers=2, d_ff=64, max_len=16, dtype=jnp.float32)
    model = bert.Bert(cfg)
    loader = DataLoader(files=paths, batch_size=16, shuffle=True, seed=0,
                        native=False)
    batcher = mlm.MLMBatcher(loader, vocab_size=meta["vocab_size"],
                             max_predictions=4, seed=0)
    example = batcher.next()
    params = jit_init(model, jnp.asarray(example["tokens"]),
                      jnp.asarray(example["token_types"]))
    ad = AutoDist(strategy_builder=AllReduce())
    step = ad.function(bert.make_mlm_loss_fn(model), params,
                       optax.adam(1e-2), example_batch=example)
    losses = [float(step(batcher.next())) for _ in range(30)]
    assert np.isfinite(losses).all()
    # The corpus is uniform-random (entropy floor ~log(40) = 3.7): training
    # should descend clearly from the initial loss toward that floor.
    assert np.mean(losses[-5:]) < losses[0] - 0.5, losses


def test_bert_eval_restores_and_scores(tmp_path, monkeypatch):
    """Train -> checkpoint -> `bert.py --eval --restore`: masked-LM accuracy
    on a cyclic (fully predictable) corpus is far above chance with the
    restored params and ~chance with a fresh init — the reference's
    masked_lm_accuracy metric driven through the benchmark CLI."""
    import optax

    from autodist_tpu import AutoDist
    from autodist_tpu.checkpoint import Saver
    from autodist_tpu.models import bert
    from autodist_tpu.models.common import jit_init
    from autodist_tpu.strategy import AllReduce

    # Cyclic corpus: word i = w{i % 8} — every masked slot is inferable from
    # its neighbors, so a trained model should approach 100%.
    corpus = str(tmp_path / "cyclic.txt")
    with open(corpus, "w") as f:
        for _ in range(400):
            f.write(" ".join(f"w{i % 8}" for i in range(40)) + "\n")

    import examples.benchmark.bert as bench

    bench.main(["--tokenize_corpus", corpus, "--data_dir",
                str(tmp_path / "shards"), "--seq_len", "16",
                "--vocab_size", "16"])

    from autodist_tpu.data import mlm
    meta = mlm.read_meta(str(tmp_path / "shards"))
    tiny = dict(d_model=32, n_heads=2, n_layers=2, d_ff=64)
    monkeypatch.setitem(bench.SIZES, "tiny", tiny)

    loader, _ = mlm.open_mlm_loader(str(tmp_path / "shards"), batch_size=16,
                                    shuffle=True)
    batcher = mlm.MLMBatcher(loader, vocab_size=meta["vocab_size"],
                             max_predictions=3, seed=0)
    cfg = bert.BertConfig(vocab_size=meta["vocab_size"], max_len=16,
                          dtype=jnp.float32, **tiny)
    model = bert.Bert(cfg)
    example = batcher.next()
    params = jit_init(model, jnp.asarray(example["tokens"]),
                      jnp.asarray(example["token_types"]))
    ad = AutoDist(strategy_builder=AllReduce())
    step = ad.function(bert.make_mlm_loss_fn(model), params,
                       optax.adam(3e-3), example_batch=example)
    for _ in range(60):
        step(batcher.next())
    loader.close()
    prefix = Saver().save(step.get_state(), str(tmp_path / "ckpt"))

    common = ["--size", "tiny", "--eval", "--data_dir",
              str(tmp_path / "shards"), "--seq_len", "16",
              "--batch_size", "16", "--max_predictions", "3"]
    # 60 tiny-model steps reach ~0.55 (10% of masked slots are random-replaced
    # and neighbors can be masked too, so 1.0 is not the ceiling); fresh init
    # sits at ~1/vocab. The GAP is what proves restore carried the learning.
    acc = bench.main(common + ["--restore", prefix])
    assert acc > 0.4, acc
    chance = bench.main(common)
    assert chance < 0.2, chance


def test_prep_validates(tmp_path):
    corpus = str(tmp_path / "tiny.txt")
    with open(corpus, "w") as f:
        f.write("a b c\n")
    vocab = Vocabulary(["a", "b", "c"])
    with pytest.raises(ValueError, match="too short"):
        mlm.prepare_mlm_shards(corpus, vocab, str(tmp_path / "x"), seq_len=2)
    with pytest.raises(ValueError, match="no MLM rows"):
        mlm.prepare_mlm_shards(corpus, vocab, str(tmp_path / "x"), seq_len=32)
