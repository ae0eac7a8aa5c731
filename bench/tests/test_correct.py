"""The comparison that decides ``correct``, at a size a CPU test run holds.

Each cell is run end to end past the harness's look for a chip, at a tiny
population, cohort and local data (token models also at small widths):
a sound run reads ``correct``; the control (a lower precision in the
program's place) and every planted fault read not correct.
"""
import os
import time

import jax
import numpy as np
import pytest

import faults
import harness
import plugins
import rehearse

SEED = 2 ** 31 + 4242


def tiny_run(name, **kw):
    cell = rehearse.tiny(harness.load_cell(name))
    return harness.run(cell, SEED, 0.0, False, t_start=time.perf_counter(),
                       require_tpu=False, **kw)


def test_reference_encode_follows_the_mechanism_stream():
    """The plain RQM encode draws the same uniforms as the program's, so
    on the same clipped vectors it gives the same levels."""
    from repro.core.mechanisms import make_mechanism

    mech = {"c": 0.02, "delta": 0.02, "m": 16, "q": 0.42}
    program = make_mechanism(dict(mech, name="rqm"))
    x = jax.random.uniform(jax.random.key(3), (3, 5000), minval=-0.03,
                           maxval=0.03)
    key = jax.random.key(11)
    want = np.asarray(program.quantize_batch(x, key))
    rqm = plugins.find("mechanisms", "rqm")
    seed = rqm.round_seed(key)
    got = np.stack([np.asarray(rqm.encode(
        x[i], seed, np.uint32(i * 5000), **mech)) for i in range(3)])
    np.testing.assert_array_equal(got, want)


def test_reference_relu_has_no_gradient_at_zero():
    """The CNN reference's ReLU passes no gradient at an exact zero, ReLU's
    usual convention. A pre-activation of exactly 0 occurs now and then on
    the TPU; there a tie's half gradient changed a client's clipped
    gradient and, through the block's later rounds, the released sums."""
    import jax.numpy as jnp

    cnn = plugins.load_path(os.path.join(harness.BENCH, "configs",
                                         "emnist_cnn.py"))
    y = jnp.array([-1.0, 0.0, 2.0])
    grad = jax.grad(lambda v: jnp.sum(cnn._relu(v)))(y)
    np.testing.assert_array_equal(np.asarray(grad), [0.0, 0.0, 1.0])


@pytest.mark.parametrize("name", ["emnist_cnn.paper40",
                                  "mamba2_370m.short_local"])
def test_sound_run_is_correct(name):
    out = tiny_run(name)
    assert out["correct"], out["compared"]


@pytest.mark.parametrize("name", ["emnist_cnn.paper40",
                                  "mamba2_370m.short_local"])
def test_control_is_not_correct(name):
    out = tiny_run(name, control=True)
    assert not out["correct"], out["compared"]


@pytest.mark.parametrize("fault", [f for f in faults.FAULTS
                                   if f != "no_exchange"])
@pytest.mark.parametrize("name", ["emnist_cnn.paper40",
                                  "mamba2_370m.short_local"])
def test_planted_fault_is_not_correct(name, fault):
    out = tiny_run(name, fault=fault)
    assert not out["correct"], out["compared"]


def test_fault_is_lifted_after_the_run():
    from repro.core.mechanisms import RQMMechanism
    from repro.fed import rounds

    before = (rounds.make_round_step, RQMMechanism.quantize_batch,
              RQMMechanism.decode_sum)
    with faults.planted("half_cohort"):
        assert RQMMechanism.decode_sum is not before[2]
    assert (rounds.make_round_step, RQMMechanism.quantize_batch,
            RQMMechanism.decode_sum) == before


@pytest.mark.parametrize("cfg_fed, traffic_fed", [
    ({}, {"num_clients": 12, "no_such_field": 1}),
    ({}, {"num_clients": 12, "seed": 3}),
    ({"lr": 0.5}, {"lr": 0.1}),
])
def test_fed_keywords_refuse_what_fedconfig_does_not_take(cfg_fed, traffic_fed):
    with pytest.raises(ValueError):
        harness.fed_keywords(cfg_fed, traffic_fed)


def test_fed_keywords_pass_every_fedconfig_field_through():
    fed = harness.fed_keywords({"lr": 0.5}, {"subsampling": "poisson",
                                             "dropout": 0.1,
                                             "fused_rounds": True})
    assert fed == {"lr": 0.5, "subsampling": "poisson", "dropout": 0.1,
                   "fused_rounds": True}


def test_pieces_are_found_by_name():
    assert plugins.find("cohorts", "fixed") is plugins.find("cohorts", "fixed")
    with pytest.raises(KeyError, match="known"):
        plugins.find("mechanisms", "no_such_mechanism")


def test_no_chip_is_refused():
    cell = harness.load_cell("emnist_cnn.paper40")
    with pytest.raises(harness.NoChip):
        harness.run(cell, SEED, 1.0, False, t_start=time.perf_counter())


def test_shard_cell_catches_the_exchange_left_out():
    """The four-chip cell on four virtual CPU devices, in a child process
    that sets its own device count: a sound run reads correct, and one
    with the SecAgg sum across chips left out does not."""
    import json
    import os
    import subprocess
    import sys

    bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import json, time, sys\n"
        f"sys.path.insert(0, {bench!r})\n"
        "import harness, rehearse\n"
        "cell = rehearse.tiny(harness.load_cell('emnist_cnn.shard4_c160'))\n"
        "out = {}\n"
        "for fault in (None, 'no_exchange'):\n"
        f"    r = harness.run(cell, {SEED}, 0.0, False,\n"
        "                    t_start=time.perf_counter(), require_tpu=False,\n"
        "                    fault=fault)\n"
        "    out[str(fault)] = [r['correct'], r['device']['count']]\n"
        "print(json.dumps(out))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], env=env, timeout=900,
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got == {"None": [True, 4], "no_exchange": [False, 4]}
