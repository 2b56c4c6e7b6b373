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
* ``tokens``: ``{"dist": "uniform", "pool": P}`` — language-model
  batches: ``P`` distinct steps of token ids drawn uniformly over the
  configuration's vocabulary, cycled through in order.
"""
from __future__ import annotations

import json
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


def load_mix(path: Path) -> dict:
    raw = json.loads(Path(path).read_text())
    unknown = set(raw) - set(DEFAULTS)
    if unknown:
        raise ValueError(f"{path}: unknown traffic keys {sorted(unknown)}")
    mix = dict(DEFAULTS, **raw)
    if mix["loop"] != "closed":
        raise ValueError(f"{path}: loop {mix['loop']!r} is not supported; "
                         "the generator drives closed loops only")
    if mix["tokens"] is not None and mix["tokens"].get("dist") != "uniform":
        raise ValueError(f"{path}: token distribution "
                         f"{mix['tokens'].get('dist')!r} is not supported")
    return mix


def program_seed(seed: int) -> int:
    """The seed handed to the program and the generators: ``--seed``
    folded into 31 bits, so every consumer sees the same value."""
    return int(seed) % (2 ** 31)


def token_batches(seed: int, *, steps: int, agents: int, batch: int,
                  seq_len: int, vocab: int) -> tuple:
    """``steps`` distinct step batches of uniform token ids, made on the
    device in one jitted call.  Each is ``{"tokens", "labels"}`` of shape
    ``(agents, batch, seq_len)`` int32, the labels being the tokens
    shifted by one."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        toks = jax.random.randint(
            key, (steps, agents, batch, seq_len + 1), 0, vocab, jnp.int32)
        return tuple({"tokens": toks[i, ..., :-1], "labels": toks[i, ..., 1:]}
                     for i in range(steps))

    return make(jax.random.fold_in(jax.random.key(program_seed(seed)), 7))
