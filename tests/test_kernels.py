"""Pallas kernel tests: bit-exact vs the pure-jnp oracle across a
shape/dtype/block sweep, tiling invariance, and distributional agreement
with the Lemma 5.1 closed form (kernel -> theory, not just kernel -> copy)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.distribution import rqm_outcome_distribution
from repro.core.grid import RQMParams, encode_value
from repro.core.pbm import PBMParams
from repro.core.qmgeo import QMGeoParams
from repro.kernels import ops, ref

PARAMS = RQMParams(c=1.0, delta=1.0, m=16, q=0.42)


def _x(shape, dtype, seed=0, c=1.0):
    return jax.random.uniform(
        jax.random.key(seed), shape, jnp.float32, -c, c
    ).astype(dtype)


class TestRQMKernel:
    @pytest.mark.parametrize("n", [1, 7, 128, 4096, 50_000])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_oracle(self, n, dtype):
        x = _x((n,), dtype)
        key = jax.random.key(42)
        z_k = ops.rqm(x, key, PARAMS, interpret=True, block_rows=8)
        z_r = ref.rqm_ref(x.astype(jnp.float32), ops.key_to_seed(key), PARAMS)
        np.testing.assert_array_equal(np.asarray(z_k), np.asarray(z_r))

    @pytest.mark.parametrize("m", [4, 8, 16, 32])
    @pytest.mark.parametrize("q", [0.2, 0.42, 0.7])
    def test_param_sweep(self, m, q):
        params = RQMParams(c=0.5, delta=0.7, m=m, q=q)
        x = _x((9001,), jnp.float32, seed=m, c=0.5)
        key = jax.random.key(m * 7 + 1)
        z_k = ops.rqm(x, key, params, interpret=True, block_rows=8)
        z_r = ref.rqm_ref(x, ops.key_to_seed(key), params)
        np.testing.assert_array_equal(np.asarray(z_k), np.asarray(z_r))
        assert 0 <= int(z_k.min()) and int(z_k.max()) <= m - 1

    @pytest.mark.parametrize("block_rows", [8, 16, 64, 256])
    def test_tiling_invariance(self, block_rows):
        """Counter-based RNG => identical levels for any block shape."""
        x = _x((20_000,), jnp.float32, seed=5)
        key = jax.random.key(9)
        base = ops.rqm(x, key, PARAMS, interpret=True, block_rows=8)
        z = ops.rqm(x, key, PARAMS, interpret=True, block_rows=block_rows)
        np.testing.assert_array_equal(np.asarray(base), np.asarray(z))

    def test_fast_path_matches_kernel(self):
        """The fused-jnp CPU path is bit-identical to the Pallas kernel."""
        x = _x((12_345,), jnp.float32, seed=2)
        key = jax.random.key(11)
        z_k = ops.rqm(x, key, PARAMS, interpret=True)
        z_f = ops.rqm_fast(x, key, PARAMS)
        np.testing.assert_array_equal(np.asarray(z_k), np.asarray(z_f))

    def test_nd_shapes(self):
        x = _x((17, 33, 5), jnp.float32, seed=3)
        z = ops.rqm(x, jax.random.key(0), PARAMS, interpret=True, block_rows=8)
        assert z.shape == x.shape and z.dtype == jnp.int32

    def test_distribution_matches_lemma51(self):
        """Kernel output histogram vs the paper's closed form."""
        n = 150_000
        xv = -0.62
        z = ops.rqm(jnp.full((n,), xv), jax.random.key(77), PARAMS,
                    interpret=True)
        hist = np.bincount(np.asarray(z), minlength=16) / n
        exact = rqm_outcome_distribution(xv, PARAMS)
        assert np.abs(hist - exact).max() < 6e-3

    def test_unbiased(self):
        n = 200_000
        xv = 0.31
        z = ops.rqm(jnp.full((n,), xv), jax.random.key(5), PARAMS, interpret=True)
        mean = float(encode_value(z, PARAMS).mean())
        assert abs(mean - xv) < 6e-3

    def test_clips_out_of_range(self):
        z_hi = ops.rqm(jnp.full((1000,), 99.0), jax.random.key(0), PARAMS,
                       interpret=True, block_rows=8)
        z_lo = ops.rqm(jnp.full((1000,), -99.0), jax.random.key(0), PARAMS,
                       interpret=True, block_rows=8)
        # clipped to +-c, which lies strictly inside the extended grid
        assert int(z_hi.max()) <= PARAMS.m - 1 and int(z_lo.min()) >= 0
        assert float(encode_value(z_hi, PARAMS).mean()) > 0.8 * PARAMS.c
        assert float(encode_value(z_lo, PARAMS).mean()) < -0.8 * PARAMS.c


class TestPBMKernel:
    @pytest.mark.parametrize("n", [64, 5000])
    def test_matches_oracle(self, n):
        params = PBMParams(c=1.0, m=16, theta=0.25)
        x = _x((n,), jnp.float32, seed=8)
        key = jax.random.key(21)
        z_k = ops.pbm(x, key, params, interpret=True, block_rows=8)
        z_r = ref.pbm_ref(x, ops.key_to_seed(key), params)
        np.testing.assert_array_equal(np.asarray(z_k), np.asarray(z_r))
        z_f = ops.pbm_fast(x, key, params)
        np.testing.assert_array_equal(np.asarray(z_k), np.asarray(z_f))

    def test_mean(self):
        params = PBMParams(c=1.0, m=16, theta=0.25)
        z = ops.pbm(jnp.full((100_000,), 0.5), jax.random.key(2), params,
                    interpret=True)
        assert abs(float(z.mean()) - 16 * (0.5 + 0.125)) < 0.05


@pytest.mark.parametrize("name,params", [
    ("rqm", PARAMS),
    ("pbm", PBMParams(c=1.0, m=16, theta=0.25)),
    ("qmgeo", QMGeoParams(c=1.0, delta=1.0, m=16, r=0.6)),
])
def test_offset_kernel_matches_fast_path(name, params):
    """A shard's slice: the kernel's element-offset operand draws exactly
    the counters the fused-jnp path draws at the same offset."""
    x = _x((3000,), jnp.float32, seed=4)
    key = jax.random.key(13)
    offset = jnp.uint32(5 * 3000)
    z_k = getattr(ops, name)(x, key, params, offset=offset, interpret=True,
                             block_rows=8)
    z_f = getattr(ops, f"{name}_fast")(x, key, params, offset=offset)
    np.testing.assert_array_equal(np.asarray(z_k), np.asarray(z_f))


class TestTreeOps:
    def test_rqm_tree(self):
        tree = {
            "a": _x((100,), jnp.float32, 1),
            "b": {"c": _x((7, 13), jnp.float32, 2)},
        }
        z = ops.rqm_tree(tree, jax.random.key(0), PARAMS, interpret=True,
                         block_rows=8)
        assert z["a"].shape == (100,) and z["b"]["c"].shape == (7, 13)
        assert z["a"].dtype == jnp.int32
