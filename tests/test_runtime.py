"""Runtime tests: checkpoint fault tolerance and the trainer loop."""

import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.crypto import rlwe
from repro.data.video import make_streams
from repro.train.checkpoint import (
    CheckpointError,
    latest_step,
    load_checkpoint,
    save_checkpoint,
)
from repro.train.trainer import SalientTrainer, TrainerConfig


# ----------------------------------------------------------------- ckpt
def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "a": jax.random.normal(k, (32, 16)),
        "b": {"c": jnp.arange(100, dtype=jnp.int32), "d": jnp.float32(3.5)},
    }


def _assert_tree_equal(x, y):
    for a, b in zip(jax.tree.leaves(x), jax.tree.leaves(y)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_roundtrip(tmp_path):
    state = _tree()
    save_checkpoint(str(tmp_path), 7, state)
    assert latest_step(str(tmp_path)) == 7
    step, loaded = load_checkpoint(str(tmp_path), state)
    assert step == 7
    _assert_tree_equal(state, loaded)


def test_checkpoint_survives_two_lost_shards(tmp_path):
    state = _tree(1)
    meta = save_checkpoint(str(tmp_path), 3, state, n_shards=5, parity="raid6")
    # destroy two shards
    os.remove(os.path.join(tmp_path, meta["shards"][1]))
    with open(os.path.join(tmp_path, meta["shards"][3]), "wb") as f:
        f.write(b"short")  # corrupt (wrong size)
    step, loaded = load_checkpoint(str(tmp_path), state)
    _assert_tree_equal(state, loaded)


def test_checkpoint_sealed_requires_secret(tmp_path):
    pub, s = rlwe.keygen(jax.random.PRNGKey(0))
    state = _tree(2)
    save_checkpoint(str(tmp_path), 1, state, seal_key=pub)
    with pytest.raises(CheckpointError):
        load_checkpoint(str(tmp_path), state)
    _, loaded = load_checkpoint(str(tmp_path), state, secret=s)
    _assert_tree_equal(state, loaded)


def test_checkpoint_picks_latest(tmp_path):
    save_checkpoint(str(tmp_path), 1, _tree(1))
    save_checkpoint(str(tmp_path), 5, _tree(5))
    step, loaded = load_checkpoint(str(tmp_path), _tree(0))
    assert step == 5
    _assert_tree_equal(_tree(5), loaded)


# ----------------------------------------------------------------- trainer
def test_salient_trainer_end_to_end(tmp_path):
    streams = make_streams(4, height=32, width=32)
    tr = SalientTrainer(streams, str(tmp_path), TrainerConfig(checkpoint_every=2))
    r1 = tr.run_step()
    r2 = tr.run_step(shard_times=[1.0, 1.0, 5.0, 1.0][: tr.cfg.n_shards])
    assert r2.step == 2
    assert np.isfinite(r1.codec_loss)
    assert r1.novel_selected >= 1
    assert r1.archived_streams + r1.novel_selected <= len(streams) + len(streams)
    # checkpoint written at step 2
    assert latest_step(str(tmp_path)) == 2
    # restart resumes from checkpoint
    tr2 = SalientTrainer(streams, str(tmp_path), TrainerConfig(checkpoint_every=2))
    assert tr2.step == 2
    _assert_tree_equal(tr.trainable, tr2.trainable)


def test_trainer_rebalances_on_straggler(tmp_path):
    streams = make_streams(6, height=32, width=32)
    tr = SalientTrainer(streams, str(tmp_path), TrainerConfig(n_shards=2))
    before = dict(tr.placement.assignment)
    rep = None
    for i in range(3):
        rep = tr.run_step(shard_times=[8.0, 1.0])
    assert rep.rebalanced or tr.placement.assignment != before
