"""The one traffic generator: token batches from a mix's data, by seed."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from chipbench_toy import ROOT  # noqa: F401  (puts the benchmark on the path)

from benchmarks.chip import traffic

SHAPE = dict(agents=2, batch=1, seq_len=16, vocab=512)


def _uniform_before_zipf(seed, *, steps, agents, batch, seq_len, vocab):
    """The generator as it was before it took a distribution: the
    uniform draw that the cells' batches must keep, bit for bit."""
    @jax.jit
    def make(key):
        toks = jax.random.randint(
            key, (steps, agents, batch, seq_len + 1), 0, vocab, jnp.int32)
        return tuple({"tokens": toks[i, ..., :-1], "labels": toks[i, ..., 1:]}
                     for i in range(steps))

    return make(jax.random.fold_in(
        jax.random.key(traffic.program_seed(seed)), 7))


def _equal(a, b) -> bool:
    return len(a) == len(b) and all(
        np.array_equal(x[k], y[k]) for x, y in zip(a, b) for k in x)


@pytest.mark.parametrize("seed", [3, 2**31 + 17, 4_000_000_011])
def test_uniform_batches_are_unchanged(seed):
    got = traffic.token_batches(seed, {"dist": "uniform", "pool": 4}, **SHAPE)
    assert _equal(got, _uniform_before_zipf(seed, steps=4, **SHAPE))


def test_zipf_batches_are_seeded():
    tok = {"dist": "zipf", "s": 1.1, "pool": 3}
    a = traffic.token_batches(2**31 + 5, tok, **SHAPE)
    assert _equal(a, traffic.token_batches(2**31 + 5, tok, **SHAPE))
    assert not _equal(a, traffic.token_batches(2**31 + 6, tok, **SHAPE))
    for b in a:
        assert b["tokens"].shape == (2, 1, 16) and b["tokens"].dtype == jnp.int32
        assert np.array_equal(b["tokens"][..., 1:], b["labels"][..., :-1])
        assert 0 <= int(b["tokens"].min()) and int(b["tokens"].max()) < 512


def test_zipf_top_rank_share():
    # rank 1 is drawn with probability 1 / sum_r r^-s; over 10^5 draws
    # the most frequent id's share lies within 10 % of it
    vocab, s, n = 49152, 1.1, 100_000
    (b,) = traffic.token_batches(11, {"dist": "zipf", "s": s, "pool": 1},
                                 agents=1, batch=1, seq_len=n, vocab=vocab)
    counts = np.bincount(np.asarray(b["tokens"]).ravel(), minlength=vocab)
    want = 1.0 / np.sum(np.arange(1, vocab + 1, dtype=np.float64) ** -s)
    assert abs(counts.max() / n - want) < 0.1 * want
    # the ranks reach ids through a permutation, not in id order
    assert int(np.argmax(counts)) != 0


def _mix(tmp_path, tokens):
    path = tmp_path / "mix.json"
    path.write_text(json.dumps({"comm": "always", "tokens": tokens}))
    return path


@pytest.mark.parametrize("tokens", [
    {"dist": "uniform", "pool": 64},
    {"dist": "zipf", "s": 1.1, "pool": 64},
    {"dist": "zipf", "s": 2, "pool": 1},
])
def test_load_mix_accepts(tmp_path, tokens):
    assert traffic.load_mix(_mix(tmp_path, tokens))["tokens"] == tokens


@pytest.mark.parametrize("tokens", [
    {"dist": "zipf", "s": 0.0, "pool": 64},
    {"dist": "zipf", "s": -1.1, "pool": 64},
    {"dist": "zipf", "s": "1.1", "pool": 64},
    {"dist": "zipf", "s": True, "pool": 64},
    {"dist": "zipf", "pool": 64},
    {"dist": "uniform", "s": 1.1, "pool": 64},
    {"dist": "uniform", "pool": 0},
    {"dist": "uniform"},
    {"dist": "normal", "pool": 64},
    {"pool": 64},
])
def test_load_mix_refuses(tmp_path, tokens):
    with pytest.raises(ValueError):
        traffic.load_mix(_mix(tmp_path, tokens))
