"""The reference's cohort for ``subsampling`` ``poisson``: each round every
one of ``num_clients`` clients is selected on its own, at the rate
``clients_per_round / num_clients``, and each selected client then drops
out at the rate ``dropout``.

As the program states its rounds (the docstrings of ``fed/cohort.py``):

* the round key splits four ways a round,
  ``key, k_sample, k_enc, k_drop = split(key, 4)``;
* ``bernoulli(k_sample, rate, (num_clients,))`` selects; the selected
  clients, in ascending order, fill the round's slate of
  ``clients_per_round + ceil(6 sigma) + 4`` places (``sigma`` the standard
  deviation of the selected count; at most ``num_clients``, or
  ``max_cohort`` where given), and a selected client past its end takes
  no part;
* ``bernoulli(k_drop, dropout, (slate,))`` over the slate's places drops
  clients out;
* each client that takes part encodes at its place in the slate, and the
  server decodes at the number that took part.
"""
from __future__ import annotations

import math

import jax
import numpy as np


def slate(fed: dict) -> int:
    """The number of places a round's cohort has."""
    n = fed["num_clients"]
    if fed.get("max_cohort") is not None:
        return min(fed["max_cohort"], n)
    rate = fed["clients_per_round"] / n
    sigma = math.sqrt(n * rate * (1.0 - rate))
    return min(n, fed["clients_per_round"] + math.ceil(6 * sigma) + 4)


def draw(key, fed: dict):
    """``(key, k_enc, members)``: the next round key, the round's encode
    key, and ``(place, client_id)`` of every client taking part."""
    key, k_sample, k_enc, k_drop = jax.random.split(key, 4)
    places = slate(fed)
    rate = fed["clients_per_round"] / fed["num_clients"]
    selected = np.asarray(jax.random.bernoulli(k_sample, rate,
                                               (fed["num_clients"],)))
    ids = np.flatnonzero(selected)[:places]
    dropped = np.zeros(places, bool)
    if fed.get("dropout", 0.0):
        dropped = np.asarray(jax.random.bernoulli(k_drop, fed["dropout"],
                                                  (places,)))
    members = [(place, int(cid)) for place, cid in enumerate(ids)
               if not dropped[place]]
    return key, k_enc, members
