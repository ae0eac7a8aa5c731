"""Multi-shard federated engine checks, run in a SUBPROCESS with 4 fake CPU
devices (the main pytest process must keep the default single device — same
contract as tests/sharded_checks.py). Invoked by tests/test_shard_engine.py.

Checks (the ISSUE-3 acceptance contract on a 4-device mesh):
  1. engine="shard" on 4 shards produces EXACTLY the scan engine's encoded
     per-round SecAgg sums (integer psum is reduction-order free), and —
     because decode of an identical integer sum is deterministic — bit-equal
     parameters;
  2. packed (16-bit lane) cross-shard aggregation == unpacked psum, via
     bit-equal trained parameters;
  3. streaming-cohort staging == full staging, bit-for-bit;
  4. the float 'none' baseline (whose partial sums ARE floats) matches scan
     to reduction-order tolerance (allclose);
  5. per-round epsilon accounts the FULL cross-shard cohort, not n/shards;
  6. under Poisson subsampling + dropout (ISSUE 4) the 4-shard engine
     realizes EXACTLY the scan engine's cohorts — same per-round realized
     sizes, encoded sums, parameters, and accounted eps sequence;
  7. the 4-shard round block carries the five device scopes (fed.grads,
     fed.client_grad, fed.encode_sum, fed.secure_sum, fed.decode_apply),
     unfused and fused — the cross-shard exchange under fed.secure_sum.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"  # fake host devices, never the chip

import jax
import numpy as np

from repro.core.mechanisms import make_mechanism
from repro.fed.loop import FedConfig, FedTrainer

SMALL = dict(num_clients=24, clients_per_round=8, rounds=4, lr=1.0,
             eval_size=64, samples_per_client=8)
ROUNDS = 4


def _train(engine, name="rqm", **overrides):
    tr = FedTrainer(make_mechanism(name, c=0.05),
                    FedConfig(engine=engine, **{**SMALL, **overrides}))
    tr.train(rounds=ROUNDS, eval_every=ROUNDS, log=lambda *_: None)
    return tr


def check_encoded_sum_equality():
    scan = _train("scan", collect_sums=True)
    shard = _train("shard", shards=4, collect_sums=True)
    assert len(scan.round_sums) == len(shard.round_sums) == ROUNDS
    for t, (a, b) in enumerate(zip(scan.round_sums, shard.round_sums)):
        np.testing.assert_array_equal(a, b,
                                      err_msg=f"round {t} encoded sums differ")
    np.testing.assert_array_equal(np.asarray(scan.flat), np.asarray(shard.flat))
    print("  4-shard encoded per-round sums == scan (exact); params bit-equal")
    return shard


def check_packed_equals_unpacked(shard):
    unpacked = _train("shard", shards=4, shard_packed=False)
    np.testing.assert_array_equal(np.asarray(shard.flat),
                                  np.asarray(unpacked.flat))
    print("  packed == unpacked cross-shard secure_sum (bit-equal params)")


def check_streaming_matches_staged(shard):
    streamed = _train("shard", shards=4, staging="stream")
    np.testing.assert_array_equal(np.asarray(shard.flat),
                                  np.asarray(streamed.flat))
    print("  streaming-cohort staging == full staging (bit-equal params)")


def check_none_mechanism_allclose():
    scan = _train("scan", name="none")
    shard = _train("shard", name="none", shards=4)
    np.testing.assert_allclose(np.asarray(scan.flat), np.asarray(shard.flat),
                               rtol=1e-5, atol=1e-7)
    print("  float 'none' baseline allclose across reduction orders")


def check_subsampled_cohort_parity():
    # max_cohort pins the poisson slate to a multiple of 4 so scan and the
    # 4-shard engine allocate the SAME static slate (see docs/privacy.md)
    kw = dict(subsampling="poisson", max_cohort=20, dropout=0.25,
              collect_sums=True)
    scan = _train("scan", **kw)
    shard = _train("shard", shards=4, **kw)
    assert scan.slate == shard.slate
    assert scan.realized_n == shard.realized_n, (scan.realized_n,
                                                 shard.realized_n)
    assert len(set(scan.realized_n)) > 1, "degenerate: constant cohorts"
    for t, (a, b) in enumerate(zip(scan.round_sums, shard.round_sums)):
        np.testing.assert_array_equal(a, b, err_msg=f"round {t}")
    np.testing.assert_array_equal(np.asarray(scan.flat),
                                  np.asarray(shard.flat))
    for a, b in zip(scan.accountant.history, shard.accountant.history):
        np.testing.assert_array_equal(a, b)
    streamed = _train("shard", shards=4, staging="stream",
                      subsampling="poisson", max_cohort=20, dropout=0.25)
    np.testing.assert_array_equal(np.asarray(scan.flat),
                                  np.asarray(streamed.flat))
    print("  4-shard poisson+dropout == scan: realized cohorts, sums, "
          "params, eps sequence (streamed staging included)")


def check_full_cohort_epsilon(shard):
    mech = shard.mech
    n = SMALL["clients_per_round"]
    alphas = FedConfig().accountant_alphas
    full = np.asarray([mech.per_round_epsilon(n, a) for a in alphas])
    per_shard = np.asarray([mech.per_round_epsilon(n // 4, a) for a in alphas])
    np.testing.assert_array_equal(shard._per_round_eps, full)
    assert not np.allclose(full, per_shard), "degenerate check"
    total = shard.accountant.rdp_epsilon(8.0)
    np.testing.assert_allclose(total, ROUNDS * mech.per_round_epsilon(n, 8.0),
                               rtol=1e-12)
    print("  per_round_epsilon uses the full cross-shard cohort n, not n/S")


def check_round_scopes():
    import re

    five = {"fed.grads", "fed.client_grad", "fed.encode_sum",
            "fed.secure_sum", "fed.decode_apply"}
    for fused in (False, True):
        tr = FedTrainer(make_mechanism("rqm", c=0.05),
                        FedConfig(engine="shard", shards=4, fused_rounds=fused,
                                  **SMALL))
        text = tr.engine._block_jit(2).lower(
            tr.flat, tr.opt_state, tr._key, tr.client_data,
        ).as_text(debug_info=True)
        scopes = set(re.findall(r"fed\.[a-z_]+", text))
        assert scopes == five, (fused, scopes)
    print("  4-shard round block carries the five device scopes "
          "(unfused and fused)")


if __name__ == "__main__":
    import sys

    if len(jax.devices()) < 4:
        print(f"NEEDS 4 DEVICES, have {len(jax.devices())}")
        sys.exit(3)
    shard = check_encoded_sum_equality()
    check_packed_equals_unpacked(shard)
    check_streaming_matches_staged(shard)
    check_none_mechanism_allclose()
    check_full_cohort_epsilon(shard)
    check_subsampled_cohort_parity()
    check_round_scopes()
    print("ALL SHARD ENGINE CHECKS PASS")
