"""The one traffic generator: it reads a mix's data file and makes inputs.

A traffic mix is ``traffic/<mix>.json``; adding a mix adds a data file
and no code.  Every input is a function of ``--seed`` alone: the same
seed gives the same inputs, on any device.

Keys a mix may hold:

* ``loop``: ``"closed"`` — the next unit of work is issued when the
  previous one completes (rounds back to back, steps back to back).
* ``warmup``: units run in set-up before the window (they compile and
  warm every shape the window uses).
* ``comm``: the communication-policy spec string the cell trains under.
* ``tokens``: language-model batches, ``P`` distinct steps of token ids
  over the configuration's vocabulary, cycled through in order:
  ``{"dist": "uniform", "pool": P}`` draws the ids uniformly;
  ``{"dist": "zipf", "s": s, "pool": P}`` draws rank r with probability
  proportional to r^-s, as word frequencies in text fall, and maps the
  ranks to ids through a permutation of the vocabulary from the seed.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

# a mix's keys and their defaults; an unknown key is refused, so a typo
# cannot silently leave a mix at its default
DEFAULTS = {
    "loop": "closed",
    "warmup": 3,
    "comm": None,
    "tokens": None,
    "about": "",
}
# the keys of a ``tokens`` entry, by distribution
TOKEN_KEYS = {"uniform": {"dist", "pool"}, "zipf": {"dist", "s", "pool"}}


def load_mix(path: Path) -> dict:
    raw = json.loads(Path(path).read_text())
    unknown = set(raw) - set(DEFAULTS)
    if unknown:
        raise ValueError(f"{path}: unknown traffic keys {sorted(unknown)}")
    mix = dict(DEFAULTS, **raw)
    if mix["loop"] != "closed":
        raise ValueError(f"{path}: loop {mix['loop']!r} is not supported; "
                         "the generator drives closed loops only")
    if mix["tokens"] is not None:
        _check_tokens(path, mix["tokens"])
    return mix


def _check_tokens(path, tok: dict) -> None:
    dist = tok.get("dist")
    if dist not in TOKEN_KEYS:
        raise ValueError(f"{path}: token distribution {dist!r} is not "
                         f"supported; known: {sorted(TOKEN_KEYS)}")
    if set(tok) != TOKEN_KEYS[dist]:
        raise ValueError(f"{path}: {dist} tokens take the keys "
                         f"{sorted(TOKEN_KEYS[dist])}, not {sorted(tok)}")
    pool = tok["pool"]
    if isinstance(pool, bool) or not isinstance(pool, int) or pool < 1:
        raise ValueError(f"{path}: token pool {pool!r} is not a positive "
                         "whole number")
    if dist == "zipf":
        s = tok["s"]
        if (isinstance(s, bool) or not isinstance(s, (int, float))
                or not math.isfinite(s) or s <= 0):
            raise ValueError(f"{path}: zipf exponent {s!r} is not a "
                             "positive number")


def program_seed(seed: int) -> int:
    """The seed handed to the program and the generators: ``--seed``
    folded into 31 bits, so every consumer sees the same value."""
    return int(seed) % (2 ** 31)


def token_batches(seed: int, tokens: dict, *, agents: int, batch: int,
                  seq_len: int, vocab: int) -> tuple:
    """``tokens["pool"]`` distinct step batches of token ids drawn as the
    mix's ``tokens`` says, made on the device in one jitted call.  Each
    is ``{"tokens", "labels"}`` of shape ``(agents, batch, seq_len)``
    int32, the labels being the tokens shifted by one."""
    import jax
    import jax.numpy as jnp

    steps = tokens["pool"]
    shape = (steps, agents, batch, seq_len + 1)

    def ids(key):
        if tokens["dist"] == "uniform":
            return jax.random.randint(key, shape, 0, vocab, jnp.int32)
        # zipf: inverse of the rank distribution's CDF, then the seeded
        # permutation from ranks to ids
        w = jnp.arange(1, vocab + 1, dtype=jnp.float32) ** -float(tokens["s"])
        cdf = jnp.cumsum(w)
        cdf = cdf / cdf[-1]
        u = jax.random.uniform(jax.random.fold_in(key, 1), shape)
        rank = jnp.minimum(jnp.searchsorted(cdf, u, side="right"), vocab - 1)
        perm = jax.random.permutation(jax.random.fold_in(key, 2), vocab)
        return perm.astype(jnp.int32)[rank]

    @jax.jit
    def make(key):
        toks = ids(key)
        return tuple({"tokens": toks[i, ..., :-1], "labels": toks[i, ..., 1:]}
                     for i in range(steps))

    return make(jax.random.fold_in(jax.random.key(program_seed(seed)), 7))
