"""Federated LM on the 2-D ("shard", "model") mesh, run in a SUBPROCESS
with 4 fake CPU devices (same contract as tests/shard_engine_checks.py).
Invoked by tests/test_fed_tasks.py.

Checks (the ISSUE-9 tentpole acceptance contract):
  1. a 2x2 (shard x model) mesh trains the lm task end to end — finite
     parameters, full cohorts accounted;
  2. at FIXED tensor parallelism the trajectory is independent of the
     client-mesh geometry: shards=2 x model_shards=2 must be bit-equal
     (per-round encoded integer sums AND trained parameters) to
     shards=1 x model_shards=2 — the cross-client aggregation is an
     integer psum with no reduction-order ambiguity, and the model-axis
     subgroups reduce the same two values either way.
     NOTE: a coordinate-wise comparison against the tp=1 run is NOT
     meaningful — ``init_params(key, cfg, tp)`` draws per-tp shaped
     arrays (e.g. embed ``(tp, V//tp, D)``; the ssm ``w_zx`` leaf packs
     z/x streams per LOCAL head group), so tp=2 is a different init
     draw AND a different flat coordinate ordering, not the same
     trajectory reassociated;
  3. privacy accounting still sees the full cross-shard cohort, never
     the per-shard or per-model-shard count — and, because epsilon
     depends only on realized cohort sizes, it is EXACTLY equal across
     tp (the one cross-tp invariant that survives the re-draw).
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"  # fake host devices, never the chip

import jax
import numpy as np

from repro.core.mechanisms import make_mechanism
from repro.fed.loop import FedConfig, FedTrainer

LM = dict(num_clients=8, clients_per_round=4, rounds=2, lr=0.5,
          samples_per_client=8,
          task="lm:model=mamba2-370m,seq_len=16,batch=1")
ROUNDS = 2


def _train(engine, **overrides):
    tr = FedTrainer(make_mechanism("rqm", c=0.05),
                    FedConfig(engine=engine, **{**LM, **overrides}))
    tr.train(rounds=ROUNDS, eval_every=ROUNDS, log=lambda *_: None)
    return tr


def check_2d_mesh_trains():
    tr = _train("shard", shards=2, model_shards=2, collect_sums=True)
    assert dict(tr._mesh.shape) == {"shard": 2, "model": 2}, tr._mesh.shape
    assert tr.engine.model_shards == 2 and tr.task.tp == 2
    flat = np.asarray(tr.flat)
    assert np.isfinite(flat).all()
    assert tr.realized_n == [4, 4]
    m = tr.evaluate()
    assert np.isfinite(m["loss"]) and m["ppl"] > 1.0
    print(f"  2x2 (shard x model) lm round trains: loss={m['loss']:.4f} "
          f"ppl={m['ppl']:.2f} dim={flat.size}")
    return tr


def check_client_mesh_geometry_invariance(tr2d):
    ref = _train("shard", shards=1, model_shards=2, collect_sums=True)
    assert dict(ref._mesh.shape) == {"shard": 1, "model": 2}, ref._mesh.shape
    assert len(ref.round_sums) == len(tr2d.round_sums) == ROUNDS
    for t, (a, b) in enumerate(zip(ref.round_sums, tr2d.round_sums)):
        assert a.dtype == np.int32
        np.testing.assert_array_equal(
            a, b, err_msg=f"round {t}: encoded sums differ across "
            f"client-mesh geometry at fixed tp=2"
        )
    np.testing.assert_array_equal(np.asarray(ref.flat), np.asarray(tr2d.flat))
    print("  encoded sums + params bit-equal across 2x2 vs 1x2 meshes")


def check_full_cohort_epsilon(tr2d):
    mech, n = tr2d.mech, LM["clients_per_round"]
    alphas = FedConfig().accountant_alphas
    full = np.asarray([mech.per_round_epsilon(n, a) for a in alphas])
    np.testing.assert_array_equal(tr2d._per_round_eps, full)
    np.testing.assert_allclose(
        tr2d.accountant.rdp_epsilon(8.0),
        ROUNDS * mech.per_round_epsilon(n, 8.0), rtol=1e-12,
    )
    # epsilon depends only on realized cohort sizes, so it is exact
    # across tp even though tp re-draws the parameterization
    tp1 = _train("shard", shards=2, model_shards=1)
    np.testing.assert_array_equal(tp1._per_round_eps, tr2d._per_round_eps)
    assert tp1.realized_n == tr2d.realized_n
    print("  epsilon accounts the full cohort n, not n/(S*M); exact across tp")


if __name__ == "__main__":
    import sys

    if len(jax.devices()) < 4:
        print(f"NEEDS 4 DEVICES, have {len(jax.devices())}")
        sys.exit(3)
    tr2d = check_2d_mesh_trains()
    check_client_mesh_geometry_invariance(tr2d)
    check_full_cohort_epsilon(tr2d)
    print("ALL LM 2-D MESH CHECKS PASS")
