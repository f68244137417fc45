"""The long-context example (examples/long_context_lm.py) runs end-to-end on
tiny shapes: single-mesh flash/dot path and the sequence-parallel (ring) path.
The measured ceilings it reproduces on a chip are documented in the README."""

import examples.long_context_lm as lc


def test_long_context_example_single_mesh():
    rate = lc.main(["--seq_len", "256", "--batch_size", "4", "--steps", "2",
                    "--d_model", "64", "--n_layers", "2", "--vocab", "256"])
    assert rate > 0


def test_long_context_example_sequence_parallel():
    rate = lc.main(["--seq_len", "256", "--batch_size", "4", "--steps", "2",
                    "--d_model", "64", "--n_layers", "2", "--vocab", "256",
                    "--seq_axis", "2"])
    assert rate > 0
