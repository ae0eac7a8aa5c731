"""The one generator of client data: every client's local dataset, drawn
from the run's seed. The configuration's ``input`` names the kind, whose
generator is ``bench/inputs/<kind>.py``; the traffic mix's ``data`` gives
its sizes, as keywords the generator must take.

The same seed gives the same data; every client's data differ.
"""
from __future__ import annotations

import numpy as np

from plugins import find


def client_data(cfg: dict, num_clients: int, data: dict,
                rng: np.random.Generator) -> dict:
    """numpy arrays with a leading (num_clients,) axis."""
    return find("inputs", cfg["input"]).generate(cfg, num_clients, rng, **data)
