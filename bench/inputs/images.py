"""Client data of input kind ``images``: ``samples_per_client`` images of
the configuration's ``image_size`` squared pixels, uniform in [0, 1), with
labels uniform over its ``num_classes``."""
from __future__ import annotations

import numpy as np


def generate(cfg: dict, num_clients: int, rng: np.random.Generator, *,
             samples_per_client: int) -> dict:
    s, k = samples_per_client, cfg["image_size"]
    return {
        "images": rng.random((num_clients, s, k, k), dtype=np.float32),
        "labels": rng.integers(0, cfg["num_classes"], (num_clients, s),
                               dtype=np.int32),
    }
