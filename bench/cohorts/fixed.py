"""The reference's cohort for ``subsampling`` ``fixed``: each round
``clients_per_round`` distinct clients, drawn uniformly from
``num_clients`` by the round's sample key, every one taking part.

The round key evolves as the program states it: three splits a round,
``key, k_sample, k_enc = split(key, 3)``.
"""
from __future__ import annotations

import jax
import numpy as np


def draw(key, fed: dict):
    """``(key, k_enc, members)``: the next round key, the round's encode
    key, and ``(row, client_id)`` of every client taking part, ``row``
    being its place in the cohort's element order."""
    if fed.get("dropout", 0.0):
        raise ValueError("the fixed cohort takes no dropout; a cell with "
                         "dropout needs a sampler of its own")
    key, k_sample, k_enc = jax.random.split(key, 3)
    ids = np.asarray(jax.random.choice(k_sample, fed["num_clients"],
                                       (fed["clients_per_round"],),
                                       replace=False))
    return key, k_enc, list(enumerate(ids.tolist()))
