"""The reference's sampler for Poisson cohorts with dropout
(``bench/cohorts/poisson.py``) at the ``poisson_dropout`` traffic mix's
population.

The sampler imports nothing of the program; here alone it is checked
against the program's own cohort draw, round after round, so that a
comparison built on it rests on the same cohorts on both sides.
"""
import json
import os

import jax
import numpy as np

import harness
import plugins

SEED = 2 ** 31 + 4242


def test_sampler_draws_the_programs_cohorts():
    from repro.fed import FedConfig, cohort

    with open(os.path.join(harness.BENCH, "traffic",
                           "poisson_dropout.json")) as f:
        fed = json.load(f)["fed"]
    cfg = FedConfig(**fed)
    ref = plugins.find("cohorts", "poisson")
    slate = cohort.base_slate(cfg)
    assert ref.slate(fed) == slate == 82
    key = jax.random.key(SEED % 2 ** 31)
    for _ in range(24):
        nxt, k_sample, _, k_drop = cohort.split_round_keys(cfg, key)
        ids, valid = cohort.sample_slate(cfg, slate, k_sample)
        part = np.asarray(cohort.participation(cfg, valid, k_drop))
        want = [(i, int(c)) for i, c in enumerate(np.asarray(ids)) if part[i]]
        ref_key, _, members = ref.draw(key, fed)
        assert members == want
        np.testing.assert_array_equal(jax.random.key_data(ref_key),
                                      jax.random.key_data(nxt))
        key = nxt
