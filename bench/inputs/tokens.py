"""Client data of input kind ``tokens``: ``batch`` sequences of ``seq_len``
token ids, uniform over the configuration's ``vocab_size``; each label is
the next token of its sequence."""
from __future__ import annotations

import numpy as np


def generate(cfg: dict, num_clients: int, rng: np.random.Generator, *,
             seq_len: int, batch: int) -> dict:
    seq = rng.integers(0, cfg["vocab_size"], (num_clients, batch, seq_len + 1),
                       dtype=np.int32)
    return {"tokens": np.ascontiguousarray(seq[..., :-1]),
            "labels": np.ascontiguousarray(seq[..., 1:])}
