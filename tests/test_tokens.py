"""``--dataset_name TOKENS``: the public layout of packed token streams
(``data/tokens.py``) and what the LM entry point asks of it."""

import numpy as np
import pytest

from commefficient_tpu.data import FedBatcher, val_batches
from commefficient_tpu.data.tokens import FedTokens, next_token_labels


def write_streams(root, lengths, valid=70, vocab=50, seed=0):
    rng = np.random.RandomState(seed)
    for c, n in enumerate(lengths):
        np.save(root / f"client{c}.npy", rng.randint(0, vocab, n)
                .astype(np.int32))
    np.save(root / "valid.npy", rng.randint(0, vocab, valid)
            .astype(np.int32))


def test_streams_are_cut_into_packed_sequences(tmp_path):
    write_streams(tmp_path, [64, 50, 33])
    ds = FedTokens(str(tmp_path), max_seq_len=16)
    # the tail that does not fill a sequence is left out
    assert ds.images_per_client.tolist() == [4, 3, 2] and len(ds) == 9
    assert ds.num_clients == 3
    tokens, labels = ds.get_flat_batch(np.array([0, 4, 8]))
    assert tokens.shape == labels.shape == (3, 16)
    assert tokens.dtype == labels.dtype == np.int32
    stream1 = np.load(tmp_path / "client1.npy")
    np.testing.assert_array_equal(tokens[1], stream1[:16])
    np.testing.assert_array_equal(labels[1, :-1], stream1[1:16])
    assert (labels[:, -1] == -1).all()
    np.testing.assert_array_equal(next_token_labels(tokens), labels)


def test_rounds_and_validation_batches_have_fixed_shapes(tmp_path):
    write_streams(tmp_path, [64] * 6)
    train = FedTokens(str(tmp_path), max_seq_len=16, seed=1)
    rounds = list(FedBatcher(train, 3, 1, seed=1).epoch())
    # 24 sequences, 3 a round until fewer than 3 clients have any left
    assert len(rounds) >= 8 and sum(r[2].sum() for r in rounds) == 24
    ids, (tokens, labels), mask = rounds[0]
    assert tokens.shape == labels.shape == (3, 1, 16) and mask.shape == (3, 1)
    valid = FedTokens(str(tmp_path), max_seq_len=16, train=False)
    (tokens, labels), mask = next(val_batches(valid, 8))
    assert tokens.shape == (8, 16) and mask.sum() == 4     # 70 // 16


def test_a_directory_without_the_layout_says_what_is_missing(tmp_path):
    with pytest.raises(FileNotFoundError, match="client<c>.npy"):
        FedTokens(str(tmp_path), max_seq_len=16)
    write_streams(tmp_path, [64, 10])
    with pytest.raises(ValueError, match="fewer than --max_seq_len"):
        FedTokens(str(tmp_path), max_seq_len=16)


@pytest.mark.parametrize("model, dataset", [
    ("nemotron_h-tiny", "SyntheticPersona"), ("gpt2-tiny", "TOKENS")])
def test_model_and_dataset_have_to_go_together(tmp_path, model, dataset):
    from commefficient_tpu.training import gpt2
    args = gpt2.build_gpt2_parser().parse_args(
        ["--model", model, "--dataset_name", dataset,
         "--dataset_dir", str(tmp_path)])
    with pytest.raises(ValueError, match="goes with"):
        gpt2.train(args, max_rounds=1, log=False)
