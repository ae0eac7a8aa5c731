"""The client-task registry: WHAT the federated round trains.

Mirrors the mechanism/engine/tracker/arrivals registries: a task is a
registered class (``@register_task``) built from the same ``"name:k=v"``
spec grammar (``FedConfig.task``), and it owns everything model- and
data-specific about a round:

  * ``init_params(key)`` — the model the server optimizes;
  * ``loss(params, batch)`` — the per-client objective over an OPAQUE
    batch pytree (the round engines never look inside a batch: they
    stage, index, and vmap whole pytrees);
  * ``client_batch(cid)`` — the client's deterministic local dataset as
    a host-side numpy pytree (fixed shapes across clients, so the
    engines can stack/stream them);
  * ``evaluate(flat, unravel)`` — held-out metrics (must report "loss").

Two registered tasks:

  * ``"emnist_cnn"`` (default) — the paper's EMNIST setup, reproducing
    the pre-registry engines bit-identically (the captured digests in
    tests/golden/fed_trajectories.json are asserted by
    tests/test_fed_tasks.py);
  * ``"lm"`` — federated private LM fine-tuning: per-client token
    batches from ``data/lm.py`` through a reduced model-zoo config
    (docs/lm_federated.md). Supports the shard engine's 2-D
    ``("shard", "model")`` mesh: per-layer tensor-parallel psums run
    INSIDE each client's loss, while the cross-client SecAgg boundary
    still carries only integers.

The model-axis contract (``supports_model_axis``): on a 2-D mesh the
engine calls ``bind_model_axis(ctx)`` once, then the round step uses
``shard_params`` (global tree -> this shard's local slices, per the
task's Meta pspecs), ``local_loss`` (the tensor-parallel loss with the
1/tp psum self-transpose correction, exactly as
``distributed.step.build_train_step_fn``), and ``gather_grads``
(Meta-aware gradient sync + all-gather back to the GLOBAL layout, so
every model shard clips/encodes the identical full-dimension vector and
the integer SecAgg sum over the client axis is replicated across the
model axis).
"""
from __future__ import annotations

import inspect
import math
from typing import ClassVar, Dict, Type

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.mechanisms import parse_mechanism_spec

_TASKS: Dict[str, Type["ClientTask"]] = {}


def register_task(name: str):
    """Class decorator: register a ClientTask subclass under ``name``."""

    def deco(cls: type) -> type:
        if not (isinstance(cls, type) and issubclass(cls, ClientTask)):
            raise TypeError(f"{cls!r} must subclass ClientTask")
        existing = _TASKS.get(name)
        if existing is not None and existing is not cls:
            raise ValueError(f"task {name!r} already registered to {existing}")
        cls.name = name
        _TASKS[name] = cls
        return cls

    return deco


def task_names() -> tuple:
    """Registered task names (stable registration order)."""
    return tuple(_TASKS)


def get_task(name: str) -> Type["ClientTask"]:
    cls = _TASKS.get(name)
    if cls is None:
        raise ValueError(
            f"unknown task {name!r}; registered: {', '.join(_TASKS)}"
        )
    return cls


def make_task(spec, fed_cfg) -> "ClientTask":
    """Build a registered task from a spec string — the shared
    ``"name:k=v,..."`` grammar. Explicit options are validated against
    the task's constructor signature (mirroring ``make_arrivals``)."""
    if isinstance(spec, ClientTask):
        return spec
    name, opts = parse_mechanism_spec(spec)
    cls = get_task(name)
    params = inspect.signature(cls.__init__).parameters
    accepted = {p for p in params if p not in ("self", "cfg")}
    unknown = set(opts) - accepted
    if unknown:
        raise ValueError(
            f"task {name!r} does not accept option(s) {sorted(unknown)}; "
            f"accepted: {sorted(accepted) if accepted else '(none)'}"
        )
    task = cls(fed_cfg, **opts)
    task.options = tuple(sorted(opts.items()))
    return task


class ClientTask:
    """One federated client workload (see module docstring).

    Batch pytrees are opaque to the engines: any dict/tuple of arrays
    with a shared leading client/sample geometry works, as long as every
    client's ``client_batch`` has identical shapes and dtypes.
    """

    name: ClassVar[str] = "?"
    # whether the task can run tensor-parallel over a 2-D
    # ("shard", "model") mesh (the shard engine's model_shards > 1)
    supports_model_axis: ClassVar[bool] = False

    # explicit spec options, set by make_task (canonical fingerprinting)
    options: tuple = ()

    def spec(self) -> str:
        """Canonical spec string: parses back to an equal task."""
        if not self.options:
            return self.name
        body = ",".join(f"{k}={v}" for k, v in self.options)
        return f"{self.name}:{body}"

    # -- model ---------------------------------------------------------------
    def init_params(self, key):
        raise NotImplementedError

    def loss(self, params, batch):
        """Scalar training loss (single-shard / tp == 1 path)."""
        raise NotImplementedError

    # -- data ----------------------------------------------------------------
    def client_batch(self, cid: int):
        """Client ``cid``'s deterministic local dataset (numpy pytree)."""
        raise NotImplementedError

    # -- eval ----------------------------------------------------------------
    def evaluate(self, flat, unravel) -> dict:
        """Held-out metrics for the flat parameter vector; must include
        ``"loss"``."""
        raise NotImplementedError

    # -- model-axis hooks (2-D mesh; tp > 1) ---------------------------------
    def bind_model_axis(self, ctx) -> None:
        """Called once by the shard engine before ``init_params`` when
        the mesh has a model axis. Default: unsupported."""
        raise ValueError(
            f"task {self.name!r} does not support a model axis "
            f"(model_shards > 1); only tasks with supports_model_axis "
            f"can run on a 2-D mesh"
        )

    def shard_params(self, params, ctx):
        raise NotImplementedError

    def local_loss(self, local_params, batch, ctx):
        raise NotImplementedError

    def gather_grads(self, local_grads, ctx):
        raise NotImplementedError


@register_task("emnist_cnn")
class EmnistCnnTask(ClientTask):
    """The paper's EMNIST CNN setup — Dirichlet non-iid partition,
    ``fed/cnn.py`` model, accuracy+loss eval on a held-out split.
    Bit-identical to the pre-registry engines (captured digests)."""

    def __init__(self, cfg):
        from repro.data.federated import FederatedPartition

        self.cfg = cfg
        self.partition = FederatedPartition(
            num_clients=cfg.num_clients,
            samples_per_client=cfg.samples_per_client,
            seed=cfg.seed,
            deform=cfg.data_deform,
            noise=cfg.data_noise,
        )
        ev_im, ev_lb = self.partition.gen.make_split(
            seed=10_000 + cfg.seed, size=cfg.eval_size
        )
        self.eval_images = jnp.asarray(ev_im)
        self.eval_labels = jnp.asarray(ev_lb)
        self._eval_jits = None

    def init_params(self, key):
        from repro.fed.cnn import cnn_init

        return cnn_init(key)

    def loss(self, params, batch):
        from repro.fed.cnn import cnn_loss

        return cnn_loss(params, batch["images"], batch["labels"])

    def client_batch(self, cid: int):
        im, lb = self.partition.client_data(int(cid))
        return {"images": im, "labels": lb}

    def evaluate(self, flat, unravel) -> dict:
        from repro.fed.cnn import cnn_accuracy, cnn_loss

        if self._eval_jits is None:
            self._eval_jits = (
                jax.jit(lambda f, im, lb: cnn_accuracy(unravel(f), im, lb)),
                jax.jit(lambda f, im, lb: cnn_loss(unravel(f), im, lb)),
            )
        acc_fn, loss_fn = self._eval_jits
        acc = float(acc_fn(flat, self.eval_images, self.eval_labels))
        loss = float(loss_fn(flat, self.eval_images, self.eval_labels))
        return {"accuracy": acc, "loss": loss}


def _model_dim(pspec) -> int:
    """Index of the 'model'-sharded dim of a Meta pspec, or -1."""
    for d, entry in enumerate(pspec):
        axes = entry if isinstance(entry, tuple) else (entry,)
        if "model" in axes:
            return d
    return -1


@register_task("lm")
class LmTask(ClientTask):
    """Federated private LM fine-tuning over the model zoo.

    Each client's local dataset is a deterministic batch of Markov token
    sequences from ``data.lm.TokenPipeline`` keyed by the client id —
    the per-client counterpart of the launcher's per-step stream. The
    loss is the zoo's next-token CE (+ MoE aux), so ANY registered
    config runs; the default is a shrunk ``mamba2-370m``.
    """

    supports_model_axis = True

    def __init__(self, cfg, model: str = "mamba2-370m", seq_len: int = 64,
                 batch: int = 2, branch: int = 4, eval_batch: int = 4,
                 eval_batches: int = 2, eval_seed: int = 9_999):
        from repro.configs.registry import get_config
        from repro.data.lm import TokenPipeline

        self.cfg = cfg
        self.model = model
        self.model_cfg = get_config(model, reduced=True)
        self.seq_len = int(seq_len)
        self.batch = int(batch)
        self.eval_batch = int(eval_batch)
        self.eval_batches = int(eval_batches)
        # client cid's fixed local data is the pipeline's batch(cid):
        # deterministic per (seed, cid), disjoint from the eval stream
        self._pipe = TokenPipeline(self.model_cfg, self.seq_len, self.batch,
                                   seed=cfg.seed, branch=int(branch))
        self._eval_pipe = TokenPipeline(self.model_cfg, self.seq_len,
                                        self.eval_batch,
                                        seed=int(eval_seed), branch=int(branch))
        self.tp = 1
        self._ctx = None
        self._meta = None
        self._eval_jit = None
        self._eval_mesh = None

    # -- model ---------------------------------------------------------------
    def init_params(self, key):
        from repro.models import model as model_lib

        return model_lib.init_params(key, self.model_cfg, tp=self.tp)

    def loss(self, params, batch):
        from repro.models import model as model_lib
        from repro.models.common import ParallelCtx

        total, _ = model_lib.loss_fn(
            params, self.model_cfg, ParallelCtx(), batch,
            remat=False, compute_dtype=jnp.float32,
        )
        return total

    # -- data ----------------------------------------------------------------
    def client_batch(self, cid: int):
        return self._pipe.batch(int(cid))

    # -- eval ----------------------------------------------------------------
    def evaluate(self, flat, unravel) -> dict:
        from repro.models import model as model_lib
        from repro.models.common import ParallelCtx

        if self._eval_jit is None:
            def ce(flat_, batch):
                params = unravel(flat_)
                if self.tp > 1:
                    params = self.shard_params(params, self._ctx)
                _, aux = model_lib.loss_fn(
                    params, self.model_cfg,
                    self._ctx if self.tp > 1 else ParallelCtx(), batch,
                    remat=False, compute_dtype=jnp.float32,
                )
                return aux["ce_loss"], aux["n_tokens"]

            if self.tp > 1:
                from jax.sharding import PartitionSpec as P

                ce = jax.shard_map(
                    ce, mesh=self._eval_mesh, in_specs=(P(), P()),
                    out_specs=(P(), P()), check_vma=False,
                )
            self._eval_jit = jax.jit(ce)
        tot_ce = tot_tok = 0.0
        for i in range(self.eval_batches):
            b = {k: jnp.asarray(v) for k, v in self._eval_pipe.batch(i).items()}
            ce_i, n_i = self._eval_jit(flat, b)
            tot_ce += float(ce_i) * float(n_i)
            tot_tok += float(n_i)
        ce_mean = tot_ce / max(tot_tok, 1.0)
        return {"loss": ce_mean, "ppl": math.exp(min(ce_mean, 30.0)),
                "eval_tokens": tot_tok}

    # -- model-axis hooks (2-D ("shard", "model") mesh) ----------------------
    def bind_model_axis(self, ctx, mesh=None) -> None:
        from repro.models import model as model_lib

        self._ctx = ctx
        self.tp = int(ctx.tp)
        self._eval_mesh = mesh
        self._meta = model_lib.param_meta(self.model_cfg, tp=self.tp,
                                          dtype=jnp.float32)

    def shard_params(self, params, ctx):
        """GLOBAL param tree -> this model shard's LOCAL slices (size
        shape[d]/tp along each Meta pspec's 'model' dim) — the same
        layout ``distributed.step``'s in_specs produce."""
        from repro.models import meta as meta_lib

        mi = ctx.model_index()

        def slice_leaf(m, p):
            d = _model_dim(m.pspec)
            if d < 0:
                return p
            size = p.shape[d] // ctx.tp
            return jax.lax.dynamic_slice_in_dim(p, mi * size, size, d)

        return meta_lib.tree_map(slice_leaf, self._meta, params)

    def local_loss(self, local_params, batch, ctx):
        """Tensor-parallel loss over LOCAL params, with the 1/tp psum
        self-transpose correction (build_train_step_fn's convention: the
        per-layer psums appear in both forward and backward, so grads of
        replicated leaves come out as per-shard partials that
        ``gather_grads``'s sync sums back to the true gradient)."""
        from repro.models import model as model_lib

        total, _ = model_lib.loss_fn(
            local_params, self.model_cfg, ctx, batch,
            remat=False, compute_dtype=jnp.float32,
        )
        return total / ctx.tp

    def gather_grads(self, local_grads, ctx):
        """LOCAL grad tree -> the GLOBAL layout, identical on every model
        shard: Meta-aware sync (psum for replicated leaves, subgroup
        ppermute-sum for duplicated ones), then a tiled all-gather along
        each leaf's 'model' dim."""
        from repro.models import meta as meta_lib

        grads = meta_lib.sync_grads(local_grads, self._meta, ctx)

        def gather_leaf(m, g):
            d = _model_dim(m.pspec)
            if d < 0:
                return g
            return jax.lax.all_gather(g, ctx.model_axis, axis=d, tiled=True)

        return meta_lib.tree_map(gather_leaf, self._meta, grads)


def tree_nbytes(tree) -> int:
    """Total bytes of a staged pytree (the staging byte counters)."""
    return int(sum(np.asarray(l).nbytes for l in jax.tree_util.tree_leaves(tree)))
