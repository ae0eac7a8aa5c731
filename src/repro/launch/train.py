"""Training launcher.

Three modes:
  * real run (CPU-feasible): reduced configs / small meshes — actually
    initializes params, streams synthetic LM batches, applies the chosen
    DP mechanism, logs loss, checkpoints.
      PYTHONPATH=src python -m repro.launch.train --arch gemma3-4b \\
          --reduced --steps 100 --mechanism rqm --batch 8 --seq 256
  * mesh run: pass --mesh-shape to run sharded (requires that many
    devices; on CPU export XLA_FLAGS=--xla_force_host_platform_device_count=N
    before launch — the dry-run module does this for the production meshes).
  * federated run: pass --fed-lm to train the SAME reduced config as a
    federated private fine-tuning problem (the "lm" client task,
    docs/lm_federated.md) — per-client token batches, clipped gradients,
    integer randomized quantization, SecAgg-sum rounds on any registered
    round engine. --steps becomes the round budget; --fed-shards /
    --model-shards select the shard engine's 1-D or 2-D
    ("shard", "model") mesh.
      PYTHONPATH=src python -m repro.launch.train --arch mamba2-370m \\
          --reduced --fed-lm --steps 20 --batch 2 --seq 64
"""
from __future__ import annotations

import argparse
import hashlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import latest_step, restore, save
from repro.configs.base import InputShape
from repro.configs.registry import get_config
from repro.core.mechanisms import accepted_options, make_mechanism, mechanism_names
from repro.data.lm import TokenPipeline
from repro.distributed.step import (
    MeshPlan,
    build_train_step_fn,
    make_train_step,
    round_privacy,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import meta as meta_lib
from repro.models import model as model_lib
from repro.models.common import ParallelCtx
from repro.optim import make_optimizer
from repro.optim.schedules import warmup_cosine
from repro.telemetry import NoopTracker, Timings, make_tracker


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--mechanism", default="rqm",
                    help="mechanism spec: a registered name or a "
                         "'name:k=v,...' string, e.g. 'rqm', "
                         "'qmgeo:c=0.05,m=16,r=0.6' "
                         f"(registered: {', '.join(mechanism_names())}); "
                         "--clip/--m/--q/--delta-ratio act as defaults")
    ap.add_argument("--clip", type=float, default=0.02)
    ap.add_argument("--m", type=int, default=16)
    ap.add_argument("--q", type=float, default=0.42)
    ap.add_argument("--delta-ratio", type=float, default=1.0)
    ap.add_argument("--target-eps", type=float, default=None,
                    help="drive the run BACKWARDS from a privacy budget: "
                         "calibrate the --mechanism family's privacy knob "
                         "(rqm q / pbm theta / qmgeo r) so the composed "
                         "(eps, --target-delta)-DP epsilon of --steps steps "
                         "hits this target (repro.privacy.calibrate); the "
                         "knob flag (e.g. --q) is then ignored")
    ap.add_argument("--target-delta", type=float, default=1e-5,
                    help="delta for --target-eps calibration")
    ap.add_argument("--lr", type=float, default=0.2)
    ap.add_argument("--server-opt", "--optimizer", dest="server_opt",
                    default="sgd",
                    help="server optimizer applied at the decode-then-"
                         "apply boundary (sgd | momentum | adam); "
                         "--optimizer is the legacy spelling")
    ap.add_argument("--packed", action="store_true")
    ap.add_argument("--mesh-shape", default=None,
                    help="e.g. 2x2 => (data,model); 2x2x2 => (pod,data,model); "
                         "a single number N is sugar for Nx1: pure client "
                         "parallelism over (data,) with a trivial model axis")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true",
                    help="restore params + optimizer state from the latest "
                         "checkpoint in --ckpt-dir and continue from that "
                         "step (the RNG key stream is replayed to the "
                         "restored step, so the continuation matches the "
                         "uninterrupted run)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--track", default=None,
                    help="tracker spec (make_mechanism-style): "
                         "'json:runs/lm.json', 'csv:runs/lm.csv', or a "
                         "'+'-joined composite; one record per step "
                         "(docs/telemetry.md)")
    ap.add_argument("--fed-lm", action="store_true",
                    help="federated private LM fine-tuning: run --arch as "
                         "the 'lm' client task through a FedTrainer "
                         "(docs/lm_federated.md); --steps is the round "
                         "budget, --batch/--seq the PER-CLIENT batch")
    ap.add_argument("--fed-engine", default="scan",
                    help="round engine spec for --fed-lm (scan | perround "
                         "| host | shard[:shards=..] | async[:..])")
    ap.add_argument("--clients", type=int, default=64,
                    help="--fed-lm population size")
    ap.add_argument("--cohort", type=int, default=8,
                    help="--fed-lm clients per round")
    ap.add_argument("--fed-shards", type=int, default=None,
                    help="--fed-lm shard-engine client shards")
    ap.add_argument("--model-shards", type=int, default=1,
                    help="--fed-lm tensor-parallel model shards: > 1 "
                         "extends the shard engine to the 2-D "
                         "('shard', 'model') mesh (needs fed-shards * "
                         "model-shards devices)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.fed_lm:
        return _fed_lm(args, ap)

    cfg = get_config(args.arch, reduced=args.reduced)
    shape = InputShape("cli", args.seq, args.batch, "train")
    plan = None
    if args.mesh_shape:
        dims = tuple(int(x) for x in args.mesh_shape.split("x"))
        if len(dims) == 1:
            # pure client parallelism: a trivial size-1 model axis keeps
            # the param pspecs (which name 'model') valid on this mesh
            dims = (dims[0], 1)
        names = ("pod", "data", "model")[-len(dims):]
        mesh = make_mesh(dims, names)
        plan = MeshPlan(
            mesh=mesh,
            client_axes=tuple(a for a in names if a != "model"),
        )
    n_clients = plan.n_clients if plan else 1
    if args.target_eps is not None:
        # Backwards mode: solve for the mechanism from the privacy budget
        # (repro.privacy.calibrate) instead of specifying the knob by hand.
        from repro.core.mechanisms import parse_mechanism_spec
        from repro.privacy.calibrate import calibrate, calibration_knobs

        name, explicit = parse_mechanism_spec(args.mechanism)
        knob = calibration_knobs().get(name)
        if knob is None:
            ap.error(f"--target-eps requires a calibratable mechanism "
                     f"({', '.join(calibration_knobs())}), got {name!r}")
        if knob.option in explicit:
            ap.error(f"--mechanism fixes {knob.option}="
                     f"{explicit[knob.option]} but --target-eps solves for "
                     f"{knob.option}; drop one of the two")
        pool = dict(c=args.clip, m=args.m, delta_ratio=args.delta_ratio)
        opts = {k: v for k, v in pool.items() if k in accepted_options(name)}
        opts.update(explicit)
        res = calibrate(
            name, target_eps=args.target_eps, target_delta=args.target_delta,
            rounds=args.steps, cohort=n_clients, **opts,
        )
        mech = res.mechanism
        print(f"[privacy] calibrated {res.describe()}")
    else:
        # CLI flags are defaults; options inline in the spec override them.
        mech = make_mechanism(
            args.mechanism, c=args.clip, m=args.m, q=args.q,
            delta_ratio=args.delta_ratio,
        )
    # Self-accounting (Mechanism API v2): the step's privacy comes from the
    # very mechanism object that encodes. RDP composes additively over steps.
    eps = round_privacy(mech, n_clients, alphas=(8.0,))[8.0]
    print(f"[privacy] {mech.describe()}: per-step aggregate eps(alpha=8) = "
          f"{eps:.4f} with n_clients={n_clients}; "
          f"total over {args.steps} steps = {eps * args.steps:.4f}")
    opt = make_optimizer(args.server_opt)
    lr_fn = warmup_cosine(args.lr, warmup=args.steps // 10 + 1, total_steps=args.steps)
    pipe = TokenPipeline(cfg, args.seq, args.batch, seed=args.seed)
    key = jax.random.key(args.seed)
    tracker = make_tracker(args.track)
    tracker.run_started({
        "kind": "lm_train", "engine": "lm_step", "arch": args.arch,
        "reduced": args.reduced, "mechanism": mech.describe(),
        "steps": args.steps, "batch": args.batch, "seq": args.seq,
        "server_opt": args.server_opt, "mesh": args.mesh_shape,
        "per_step_eps_alpha8": eps, "backend": jax.default_backend(),
    })

    if plan is not None:
        mesh = plan.mesh
        step_fn, specs = make_train_step(
            cfg, plan, mech, opt, lr_fn, shape, packed=args.packed,
            compute_dtype=jnp.float32,
        )
        tp = plan.tp
        with jax.set_mesh(mesh):
            params = model_lib.init_params(jax.random.key(args.seed + 1), cfg, tp=tp)
            params = jax.device_put(params, meta_lib.shardings(specs["param_meta"], mesh))
            opt_state = opt.init(params)
            # restored leaves must come back with the SAME shardings the
            # non-resume path commits (restore() yields default-device
            # arrays; re-sharding keeps large models from landing on one
            # device and the first donated step from recompiling)
            shardings = {
                "params": meta_lib.shardings(specs["param_meta"], mesh),
                "opt": meta_lib.shardings(
                    opt.state_meta(specs["param_meta"]), mesh
                ),
            }
            params, opt_state, key, start = _maybe_resume(
                args, params, opt_state, key, shardings
            )
            run_step = lambda p, o, s, b, k: step_fn(p, o, s, b, k)
            _loop(args, cfg, pipe, run_step, params, opt_state, key, start,
                  tracker=tracker, mech_desc=mech.describe())
    else:
        ctx = ParallelCtx()
        body = build_train_step_fn(
            cfg, mech, opt, lr_fn, ctx, compute_dtype=jnp.float32,
            packed=args.packed,
        )
        step_fn = jax.jit(body, donate_argnums=(0, 1))
        params = model_lib.init_params(jax.random.key(args.seed + 1), cfg, tp=1)
        opt_state = opt.init(params)
        params, opt_state, key, start = _maybe_resume(
            args, params, opt_state, key
        )
        _loop(args, cfg, pipe, step_fn, params, opt_state, key, start,
              tracker=tracker, mech_desc=mech.describe())


def _fed_lm(args, ap):
    """--fed-lm: the federated counterpart of the per-step LM run — the
    'lm' client task (fed/tasks.py) on any registered round engine, with
    the full FedTrainer surface (privacy accounting, checkpoints on
    round boundaries, tracker records per round)."""
    from repro.fed import FedConfig, FedTrainer

    if args.target_eps is not None:
        ap.error("--fed-lm does not take --target-eps yet: calibrate the "
                 "mechanism against the cohort with repro.privacy.calibrate "
                 "and pass the resulting spec via --mechanism")
    if args.mesh_shape:
        ap.error("--fed-lm meshes come from the round engine: use "
                 "--fed-engine shard with --fed-shards/--model-shards "
                 "instead of --mesh-shape")
    if not args.reduced:
        ap.error("--fed-lm requires --reduced (federated fine-tuning of "
                 "the full-size configs is not CPU-feasible)")
    mech = make_mechanism(
        args.mechanism, c=args.clip, m=args.m, q=args.q,
        delta_ratio=args.delta_ratio,
    )
    task = (f"lm:model={args.arch},seq_len={args.seq},"
            f"batch={args.batch}")
    cfg = FedConfig(
        engine=args.fed_engine, task=task, rounds=args.steps,
        num_clients=args.clients, clients_per_round=args.cohort,
        lr=args.lr, seed=args.seed, server_opt=args.server_opt,
        shards=args.fed_shards, model_shards=args.model_shards,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
    )
    tr = FedTrainer(mech, cfg, tracker=make_tracker(args.track))
    eps = tr._per_round_eps[0] if len(tr._per_round_eps) else float("nan")
    print(f"[fed-lm] task={tr.task.spec()} engine={cfg.engine} "
          f"dim={int(tr.flat.size)} cohort={args.cohort}/{args.clients} "
          f"per-round eps(alpha={cfg.accountant_alphas[0]:g})={eps:.4f}")
    start = 0
    if args.resume:
        if not args.ckpt_dir:
            raise SystemExit("--resume requires --ckpt-dir")
        start = tr.restore_checkpoint()
        print(f"[resume] restored round {start} from {args.ckpt_dir}")
    tr.train(rounds=args.steps - start,
             eval_every=max(args.log_every, 1))


def _opt_fingerprint(server_opt: str) -> np.ndarray:
    """(32,) uint8 sha256 of the optimizer name — saved with every
    checkpoint so --resume can refuse a mismatched --server-opt instead
    of silently dropping (or failing to find) the optimizer state."""
    return np.frombuffer(hashlib.sha256(server_opt.encode()).digest(),
                         np.uint8)


def _maybe_resume(args, params, opt_state, key, shardings=None):
    """--resume: restore {params, opt, key} from the latest checkpoint in
    --ckpt-dir — the saved RNG key is the post-step carry, so the
    continuation matches the uninterrupted run exactly (the data pipeline
    is stateless per step). On a mesh run, ``shardings`` re-commits the
    restored trees to the mesh (restore() returns default-device arrays).
    Returns the (possibly restored) state and the start step."""
    if not args.resume:
        return params, opt_state, key, 0
    if not args.ckpt_dir:
        raise SystemExit("--resume requires --ckpt-dir")
    step0 = latest_step(args.ckpt_dir)
    if step0 is None:
        print(f"[resume] no checkpoints in {args.ckpt_dir}; starting fresh")
        return params, opt_state, key, 0
    # fingerprint first, alone: a mismatched --server-opt may not even
    # share the checkpoint's optimizer-state tree, which would abort the
    # full restore with a missing-leaf error before this clearer one
    try:
        fp = restore(args.ckpt_dir, step0,
                     {"server_opt_fp": np.zeros(32, np.uint8)})
    except KeyError:
        raise SystemExit(
            f"--resume: checkpoint step {step0} in {args.ckpt_dir} "
            f"predates the resume metadata (no optimizer fingerprint / "
            f"RNG key saved) and cannot be resumed exactly; re-train "
            f"with this build to produce resumable checkpoints"
        )
    if not np.array_equal(fp["server_opt_fp"],
                          _opt_fingerprint(args.server_opt)):
        raise SystemExit(
            f"--resume: the checkpoint in {args.ckpt_dir} was written "
            f"with a different --server-opt than {args.server_opt!r}; "
            f"pass the original optimizer (continuing with another would "
            f"silently diverge from the uninterrupted run)"
        )
    tree = restore(args.ckpt_dir, step0,
                   {"params": params, "opt": opt_state,
                    "key": jax.random.key_data(key)})
    params, opt_state = tree["params"], tree["opt"]
    key = jax.random.wrap_key_data(tree["key"])
    if shardings is not None:
        params = jax.device_put(params, shardings["params"])
        opt_state = jax.device_put(opt_state, shardings["opt"])
    print(f"[resume] restored step {step0} from {args.ckpt_dir}")
    return params, opt_state, key, step0


def _loop(args, cfg, pipe, step_fn, params, opt_state, key, start=0,
          tracker=None, mech_desc=""):
    tracker = make_tracker(tracker)
    tracked = not isinstance(tracker, NoopTracker)
    timings = Timings()
    t0 = time.time()
    for step in range(start, args.steps):
        ts = time.perf_counter()
        with timings.scope("step"):
            batch = {k: jnp.asarray(v) for k, v in pipe.batch(step).items()}
            key, sub = jax.random.split(key)
            params, opt_state, metrics = step_fn(
                params, opt_state, jnp.int32(step), batch, sub
            )
            if tracked:
                # reading metrics blocks on the step: the tracked rate is
                # the real step rate, not the async enqueue rate
                metrics = {k: float(v) for k, v in metrics.items()}
        if tracked:
            elapsed = time.perf_counter() - ts
            tracker.log_round({
                "round": step + 1, "engine": "lm_step",
                "mechanism": mech_desc, "loss": metrics["loss"],
                "rounds_per_sec": 1.0 / max(elapsed, 1e-9),
                "extra": {
                    "ce_loss": metrics["ce_loss"],
                    "tokens_per_sec": args.batch * args.seq / max(elapsed,
                                                                  1e-9),
                },
            })
        if (step + 1) % args.log_every == 0 or step == start:
            m = {k: float(v) for k, v in metrics.items()}
            rate = (step + 1 - start) * args.batch * args.seq / (time.time() - t0)
            print(f"step {step+1:5d} loss={m['loss']:.4f} ce={m['ce_loss']:.4f} "
                  f"tok/s={rate:,.0f}", flush=True)
        if args.ckpt_dir and args.ckpt_every and (step + 1) % args.ckpt_every == 0:
            save(args.ckpt_dir, step + 1,
                 {"params": params, "opt": opt_state,
                  "key": jax.random.key_data(key),
                  "server_opt_fp": _opt_fingerprint(args.server_opt)})
    if tracked:
        tracker.log_timings(timings.summary())
    tracker.close()
    print(f"done in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
