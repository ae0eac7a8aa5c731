"""Substrate tests: data pipelines, checkpointing, optimizers, schedules,
the persistent compile cache, federated loop integration."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_step, restore, save
from repro.configs.registry import get_config
from repro.core.mechanisms import make_mechanism
from repro.data.emnist import NUM_CLASSES, SyntheticEMNIST
from repro.data.federated import FederatedPartition, sample_clients
from repro.data.lm import TokenPipeline
from repro.fed.loop import FedConfig, FedTrainer
from repro.launch import compile_cache
from repro.optim import adam, make_optimizer
from repro.optim.schedules import cosine_decay, warmup_cosine


class TestEMNIST:
    def test_deterministic(self):
        a = SyntheticEMNIST(seed=3).make_split(seed=1, size=16)
        b = SyntheticEMNIST(seed=3).make_split(seed=1, size=16)
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_array_equal(a[1], b[1])

    def test_shapes_and_labels(self):
        images, labels = SyntheticEMNIST().make_split(seed=0, size=32)
        assert images.shape == (32, 28, 28)
        assert labels.min() >= 0 and labels.max() < NUM_CLASSES

    def test_class_separability(self):
        """Prototype-nearest-neighbor beats chance by a wide margin (needed
        for the Fig-3 ordering experiment to be meaningful)."""
        gen = SyntheticEMNIST(seed=0)
        images, labels = gen.make_split(seed=2, size=400)
        flat = images.reshape(400, -1)
        protos = gen.prototypes.reshape(NUM_CLASSES, -1)
        pred = np.argmin(
            ((flat[:, None] - protos[None]) ** 2).sum(-1), axis=1
        )
        assert (pred == labels).mean() > 0.5  # chance = 1/62


class TestFederatedPartition:
    def test_client_data_deterministic(self):
        p = FederatedPartition(num_clients=10, samples_per_client=5, seed=1)
        a = p.client_data(3)
        b = p.client_data(3)
        np.testing.assert_array_equal(a[0], b[0])

    def test_sampling_without_replacement(self):
        rng = np.random.default_rng(0)
        ids = sample_clients(rng, 100, 40)
        assert len(set(ids.tolist())) == 40


class TestTokenPipeline:
    def test_deterministic_and_shapes(self):
        cfg = get_config("gemma3-4b", reduced=True)
        pipe = TokenPipeline(cfg, seq_len=64, global_batch=4, seed=0)
        b1, b2 = pipe.batch(7), pipe.batch(7)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
        assert b1["tokens"].shape == (4, 64)
        assert b1["labels"].shape == (4, 64)
        # next-token alignment
        np.testing.assert_array_equal(b1["labels"][:, :-1] * 0 + 1,
                                      (b1["labels"][:, :-1] >= 0).astype(int))

    def test_prefix_arch(self):
        cfg = get_config("pixtral-12b", reduced=True)
        pipe = TokenPipeline(cfg, seq_len=64, global_batch=2, seed=0)
        b = pipe.batch(0)
        P = cfg.frontend.prefix_len
        assert b["tokens"].shape == (2, 64 - P)
        assert b["prefix_embeds"].shape == (2, P, cfg.d_model)
        assert (b["labels"][:, :P] == -1).all()

    def test_learnable(self):
        """Markov stream: bigram statistics are concentrated (learnable)."""
        cfg = get_config("gemma3-4b", reduced=True)
        pipe = TokenPipeline(cfg, seq_len=256, global_batch=8, seed=0)
        b = pipe.batch(0)
        toks = b["tokens"]
        # successors of any token come from a branch-limited set
        succ = {}
        for row in np.asarray(toks):
            for a, c in zip(row[:-1], row[1:]):
                succ.setdefault(int(a), set()).add(int(c))
        sizes = [len(v) for v in succ.values()]
        assert np.mean(sizes) <= pipe.branch


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        tree = {
            "a": jnp.arange(10, dtype=jnp.float32),
            "nested": {"b": jnp.ones((3, 4), jnp.bfloat16)},
            "t": (jnp.zeros(2), jnp.int32(7)),
        }
        d = str(tmp_path / "ckpt")
        save(d, 42, tree)
        assert latest_step(d) == 42
        out = restore(d, 42, tree)
        for a, b in zip(jax.tree_util.tree_leaves(tree),
                        jax.tree_util.tree_leaves(out)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
            assert a.dtype == b.dtype

    def test_shape_mismatch_raises(self, tmp_path):
        d = str(tmp_path / "c")
        save(d, 1, {"a": jnp.zeros(3)})
        with pytest.raises(ValueError):
            restore(d, 1, {"a": jnp.zeros(4)})

    def test_missing_leaf_raises(self, tmp_path):
        d = str(tmp_path / "c")
        save(d, 1, {"a": jnp.zeros(3)})
        with pytest.raises(KeyError):
            restore(d, 1, {"a": jnp.zeros(3), "b": jnp.zeros(1)})


class TestOptimizers:
    def _quad(self, opt, steps=60, lr=0.1):
        params = {"w": jnp.array([3.0, -2.0])}
        state = opt.init(params)
        for step in range(steps):
            grads = {"w": 2 * params["w"]}  # d/dw ||w||^2
            params, state = opt.update(grads, state, params, jnp.float32(lr))
        return float(jnp.abs(params["w"]).max())

    @pytest.mark.parametrize("name", ["sgd", "momentum", "adam"])
    def test_converges_on_quadratic(self, name):
        opt = make_optimizer(name)
        # adam's step is ~lr regardless of curvature: give it enough steps
        steps = 200 if name == "adam" else 60
        assert self._quad(opt, steps=steps, lr=0.05) < 0.3

    def test_state_meta_shapes(self):
        from repro.models import meta as meta_lib
        from repro.models import model as model_lib

        cfg = get_config("gemma3-4b", reduced=True)
        meta = model_lib.param_meta(cfg, tp=1)
        opt = adam()
        om = opt.state_meta(meta)
        params = model_lib.init_params(jax.random.key(0), cfg, tp=1)
        st = opt.init(params)
        m_leaves = jax.tree_util.tree_leaves(om, is_leaf=meta_lib.is_meta)
        s_leaves = jax.tree_util.tree_leaves(st)
        assert len(m_leaves) == len(s_leaves)
        for m, s in zip(m_leaves, s_leaves):
            assert tuple(m.shape) == tuple(s.shape)


class TestSchedules:
    def test_warmup_cosine(self):
        f = warmup_cosine(1.0, warmup=10, total_steps=100)
        assert float(f(jnp.int32(0))) == pytest.approx(0.0)
        assert float(f(jnp.int32(10))) == pytest.approx(1.0, abs=1e-3)
        assert float(f(jnp.int32(100))) < 0.2

    def test_cosine_endpoints(self):
        f = cosine_decay(2.0, 50, final_frac=0.1)
        assert float(f(jnp.int32(0))) == pytest.approx(2.0)
        assert float(f(jnp.int32(50))) == pytest.approx(0.2, rel=1e-3)


class TestCompileCache:
    @pytest.fixture(autouse=True)
    def _restore_cache_config(self):
        from jax.experimental.compilation_cache import compilation_cache

        names = ("jax_compilation_cache_dir",
                 "jax_persistent_cache_min_compile_time_secs",
                 "jax_persistent_cache_min_entry_size_bytes")
        saved = {n: getattr(jax.config, n) for n in names}
        yield
        for n, v in saved.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()

    def test_env_dir_is_used_and_receives_compiles(self, tmp_path,
                                                  monkeypatch):
        from jax.experimental.compilation_cache import compilation_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        compilation_cache.reset_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        jax.jit(lambda x: x * 7 - 2)(jnp.arange(3.0)).block_until_ready()
        assert os.listdir(tmp_path), "no compiled program landed in the cache"

    def test_default_dir_is_fixed_under_the_repo(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(root, ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want


class TestFedLoop:
    def test_loss_decreases_and_accounting(self):
        c = 0.02
        mech = make_mechanism("rqm", c=c)
        fcfg = FedConfig(num_clients=60, clients_per_round=8, rounds=20,
                         lr=1.0, eval_size=200,
                         accountant_alphas=(2.0, 8.0))
        # self-accounting: the trainer queries mech.per_round_epsilon itself
        tr = FedTrainer(mech, fcfg)
        before = tr.evaluate()["loss"]
        hist = tr.train(rounds=20, eval_every=20, log=lambda *_: None)
        after = hist[-1]["loss"]
        assert after < before
        assert tr.accountant.rounds == 20
        assert tr.accountant.rdp_epsilon(2.0) > 0
        eps, alpha = tr.accountant.dp_epsilon(1e-5)
        assert np.isfinite(eps)

    def test_mechanisms_run(self):
        for name in ("none", "pbm", "qmgeo"):
            mech = make_mechanism(name, c=0.02)
            fcfg = FedConfig(num_clients=30, clients_per_round=5, rounds=3,
                             eval_size=50)
            tr = FedTrainer(mech, fcfg)
            tr.train(rounds=3, eval_every=3, log=lambda *_: None)
