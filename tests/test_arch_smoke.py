"""Per-architecture smoke tests (assignment contract): a REDUCED variant of
each family (<=2 layers, d_model<=512, <=4 experts) runs one forward/train
step AND one serve step on CPU, asserting output shapes and no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import InputShape
from repro.configs.registry import ARCH_IDS, get_config
from repro.core.mechanisms import make_mechanism
from repro.distributed.step import build_train_step_fn
from repro.models import model as model_lib
from repro.models.common import ParallelCtx
from repro.optim import make_optimizer
from repro.optim.schedules import constant

CTX = ParallelCtx()


def _batch(cfg, B=2, S=128, seed=0):
    key = jax.random.key(seed)
    Pfx = cfg.frontend.prefix_len if cfg.frontend else 0
    tokens = jax.random.randint(key, (B, S - Pfx), 0, cfg.vocab_size)
    labels = jnp.concatenate(
        [jnp.full((B, Pfx), -1, jnp.int32),
         jax.random.randint(key, (B, S - Pfx), 0, cfg.vocab_size)], axis=1)
    out = {"tokens": tokens, "labels": labels}
    if Pfx:
        out["prefix_embeds"] = jax.random.normal(key, (B, Pfx, cfg.d_model)) * 0.02
    return out


@pytest.mark.parametrize("arch", ARCH_IDS)
class TestArchSmoke:
    def test_reduced_limits(self, arch):
        cfg = get_config(arch, reduced=True)
        assert cfg.num_layers <= 2
        assert cfg.d_model <= 512
        if cfg.moe is not None:
            assert cfg.moe.num_experts <= 4

    def test_train_step(self, arch):
        cfg = get_config(arch, reduced=True)
        mech = make_mechanism("rqm", c=0.05)
        opt = make_optimizer("sgd")
        step = build_train_step_fn(
            cfg, mech, opt, constant(0.1), CTX, remat=False,
            compute_dtype=jnp.float32,
        )
        params = model_lib.init_params(jax.random.key(0), cfg, tp=1)
        opt_state = opt.init(params)
        batch = _batch(cfg)
        p2, o2, metrics = jax.jit(step)(
            params, opt_state, jnp.int32(0), batch, jax.random.key(1)
        )
        loss = float(metrics["loss"])
        assert np.isfinite(loss) and 0 < loss < 20
        # params moved, structure/shape preserved, all finite
        same = jax.tree_util.tree_map(lambda a, b: a.shape == b.shape, params, p2)
        assert all(jax.tree_util.tree_leaves(same))
        finite = jax.tree_util.tree_map(
            lambda t: bool(jnp.all(jnp.isfinite(t))), p2
        )
        assert all(jax.tree_util.tree_leaves(finite))
        moved = jax.tree_util.tree_map(
            lambda a, b: bool(jnp.any(a != b)), params, p2
        )
        assert any(jax.tree_util.tree_leaves(moved))

    def test_forward_shapes(self, arch):
        cfg = get_config(arch, reduced=True)
        params = model_lib.init_params(jax.random.key(0), cfg, tp=1)
        batch = _batch(cfg, B=2, S=64)
        h, aux = model_lib.forward_hidden(
            params, cfg, CTX, batch["tokens"], batch.get("prefix_embeds"),
            remat=False, compute_dtype=jnp.float32,
        )
        assert h.shape == (2, 64, cfg.d_model)
        assert bool(jnp.all(jnp.isfinite(h)))

    def test_serve_step(self, arch):
        cfg = get_config(arch, reduced=True)
        params = model_lib.init_params(jax.random.key(0), cfg, tp=1)
        B, CAP, PROMPT = 2, 96, 64
        shape = InputShape("t", CAP, B, "decode")
        Pfx = cfg.frontend.prefix_len if cfg.frontend else 0
        key = jax.random.key(1)
        toks = jax.random.randint(key, (B, PROMPT - Pfx), 0, cfg.vocab_size)
        pe = (jax.random.normal(key, (B, Pfx, cfg.d_model)) * 0.02) if Pfx else None
        nxt, caches = model_lib.prefill(
            params, cfg, CTX, toks, shape, prefix_embeds=pe,
            compute_dtype=jnp.float32,
        )
        assert nxt.shape == (B,)
        for i in range(2):
            nxt, caches = model_lib.decode_step(
                params, caches, cfg, CTX, nxt[:, None], jnp.int32(PROMPT + i),
                compute_dtype=jnp.float32,
            )
        assert nxt.shape == (B,)
        assert int(nxt.min()) >= 0 and int(nxt.max()) < cfg.padded_vocab(1)


class TestDecodeConsistency:
    """Teacher-forced forward and incremental decode agree on next tokens."""

    @pytest.mark.parametrize("arch", ["gemma3-4b", "h2o-danube-3-4b",
                                      "mamba2-370m", "chatglm3-6b",
                                      "granite-4.0-h-micro"])
    def test_prefill_matches_forward(self, arch):
        cfg = get_config(arch, reduced=True)
        params = model_lib.init_params(jax.random.key(0), cfg, tp=1)
        B, PROMPT = 2, 64
        toks = jax.random.randint(jax.random.key(1), (B, PROMPT), 0,
                                  cfg.vocab_size)
        shape = InputShape("t", 96, B, "decode")
        nxt, caches = model_lib.prefill(
            params, cfg, CTX, toks, shape, compute_dtype=jnp.float32)
        h, _ = model_lib.forward_hidden(
            params, cfg, CTX, toks, remat=False, compute_dtype=jnp.float32)
        from repro.models.common import rms_norm

        h = rms_norm(h, params["final_norm"])
        ref = model_lib.lm_head_argmax(params, CTX, h[:, -1])
        np.testing.assert_array_equal(np.asarray(nxt), np.asarray(ref))

    # gemma3-4b decode vs teacher-forced forward: fixed — the chunked
    # sliding-window forward let queries before the window filled attend
    # the zero-vector front-padding keys (attention._attend_chunk now
    # masks k_pos < 0); the decode path had been correct all along.
    @pytest.mark.parametrize("arch", [
        "gemma3-4b",
        "mamba2-370m",
        "zamba2-1.2b",
        "granite-4.0-h-micro",
    ])
    def test_decode_matches_forward(self, arch):
        """Decode one token, compare against teacher-forced forward on the
        extended sequence."""
        cfg = get_config(arch, reduced=True)
        params = model_lib.init_params(jax.random.key(0), cfg, tp=1)
        B, PROMPT = 2, 64
        toks = jax.random.randint(jax.random.key(1), (B, PROMPT), 0,
                                  cfg.vocab_size)
        shape = InputShape("t", 96, B, "decode")
        nxt, caches = model_lib.prefill(
            params, cfg, CTX, toks, shape, compute_dtype=jnp.float32)
        tok2, _ = model_lib.decode_step(
            params, caches, cfg, CTX, nxt[:, None], jnp.int32(PROMPT),
            compute_dtype=jnp.float32)
        # teacher-forced: forward over PROMPT+1 tokens
        ext = jnp.concatenate([toks, nxt[:, None]], axis=1)
        h, _ = model_lib.forward_hidden(
            params, cfg, CTX, ext, remat=False, compute_dtype=jnp.float32)
        from repro.models.common import rms_norm

        h = rms_norm(h, params["final_norm"])
        ref = model_lib.lm_head_argmax(params, CTX, h[:, -1])
        np.testing.assert_array_equal(np.asarray(tok2), np.asarray(ref))
