"""END-TO-END DRIVER — the paper's experiment (Sec 6.2 / Fig 3): federated
DP-SGD on (synthetic-)EMNIST with RQM, vs PBM, the QMGeo-style
truncated-geometric quantizer, and noise-free clipped SGD, with exact
Renyi accounting across rounds.

A few hundred rounds on CPU:

  PYTHONPATH=src python examples/fl_emnist.py --rounds 300
  PYTHONPATH=src python examples/fl_emnist.py --rounds 300 --mechanism rqm \\
      --delta-ratio 0.66 --q 0.33       # the paper's best (Δ,q) pair
  PYTHONPATH=src python examples/fl_emnist.py --rounds 300 \\
      --mechanism "qmgeo:c=0.02,m=16,r=0.6"   # any registered spec string

Privacy is SELF-ACCOUNTED: the mechanism object that encodes also answers
``per_round_epsilon(n, alpha)``, so the reported accuracy-vs-epsilon
tradeoff is computed from the exact parameters that produced the updates.

Backwards mode (--target-eps): instead of specifying privacy knobs, give a
budget and let repro.privacy.calibrate solve each family's knob so the
whole run composes to the target (eps, --target-delta)-DP; the trainer
then logs the remaining budget and halts at exhaustion. Realistic cohorts:
--subsampling poisson samples each client i.i.d. per round, --dropout
drops selected clients i.i.d. — accounting composes at the realized size.

  PYTHONPATH=src python examples/fl_emnist.py --rounds 300 \\
      --target-eps 30 --subsampling poisson --dropout 0.1
"""
import argparse
import dataclasses
import hashlib
import json
import os

from repro.core.mechanisms import (
    accepted_options,
    make_mechanism,
    mechanism_names,
    parse_mechanism_spec,
)
from repro.fed import FedConfig, FedTrainer
from repro.fed.engine import engine_names
from repro.launch.compile_cache import enable_compile_cache
from repro.privacy.calibrate import DEFAULT_ALPHAS, calibrate, calibration_knobs
from repro.telemetry import parse_tracker_spec


def _suffix_track_spec(spec: str, tag: str) -> str:
    """Per-mechanism tracker paths in a multi-mechanism sweep: insert the
    mechanism tag before the extension of every path in the spec — the
    same no-interleaving rule the per-mechanism checkpoint subdirs follow."""
    parts = []
    for sub in spec.split("+"):
        name, opts = parse_tracker_spec(sub)
        if opts.get("path"):
            root, ext = os.path.splitext(str(opts["path"]))
            opts["path"] = f"{root}-{tag}{ext}"
        body = ",".join(f"{k}={v}" for k, v in opts.items())
        parts.append(f"{name}:{body}" if body else name)
    return "+".join(parts)


def run_one(spec, fcfg, target_eps=None, resume=False, track=None,
            multi=False, **defaults):
    """One mechanism end-to-end: build from the spec (or calibrate the
    family to --target-eps), train with the configured round engine
    (resuming from the mechanism's checkpoint directory when asked),
    report the mechanism's own accounting."""
    calibrated = None
    name, explicit = parse_mechanism_spec(spec)
    if target_eps is not None and name in calibration_knobs():
        # spec strings participate too: inline options become fixed
        # calibration options — fixing the knob itself inside the spec
        # conflicts with solving for it, and calibrate() raises on it
        knob = calibration_knobs()[name]
        opts = {k: v for k, v in defaults.items()
                if k in accepted_options(name) and k != knob.option}
        opts.update(explicit)
        calibrated = calibrate(
            name, target_eps=target_eps, target_delta=fcfg.budget_delta,
            rounds=fcfg.rounds, cohort=fcfg.clients_per_round, **opts,
        )
        mech = calibrated.mechanism
        print(f"[{name}] calibrated: {calibrated.describe()}")
    else:
        mech = make_mechanism(spec, **defaults)
    # one artifact per FULL mechanism spec (family name + an 8-hex digest
    # of the exact parameters): a multi-mechanism sweep must not
    # interleave checkpoints or tracker series, and two runs of the same
    # family with different knobs (or different calibrations) must not
    # clobber each other's files
    tag = f"{name}-{hashlib.sha256(mech.describe().encode()).hexdigest()[:8]}"
    if fcfg.ckpt_dir:
        fcfg = dataclasses.replace(
            fcfg, ckpt_dir=os.path.join(fcfg.ckpt_dir, tag)
        )
    if track and multi:
        track = _suffix_track_spec(track, tag)
    tr = FedTrainer(mech, fcfg, tracker=track)
    remaining = fcfg.rounds
    if resume:
        try:
            restored = tr.restore_checkpoint()
        except FileNotFoundError:
            print(f"[{name}] no checkpoints in {fcfg.ckpt_dir}; "
                  f"starting fresh")
        else:
            remaining = max(fcfg.rounds - restored, 0)
            if remaining == 0:
                print(f"[{name}] checkpoint at round {restored} already "
                      f"covers --rounds {fcfg.rounds}; nothing to train "
                      f"(reporting the restored state)")
            else:
                print(f"[{name}] resumed from round {restored} "
                      f"({fcfg.ckpt_dir}); {remaining} rounds to go")
    hist = tr.train(rounds=remaining, eval_every=25)
    if not hist:
        # nothing left to train (resume at/beyond --rounds): still report
        # the restored model instead of an empty history
        m = tr.evaluate()
        m["round"] = tr.accountant.rounds
        if fcfg.budget_eps is not None:
            m["eps_spent"], m["eps_remaining"] = tr.budget_spent()
        hist = [m]
    out = {"mechanism": mech.name, "spec": mech.describe(), "history": hist}
    if calibrated is not None:
        out["calibration"] = {
            "target_eps": calibrated.target_eps, "knob": calibrated.knob,
            "value": calibrated.value, "epsilon": calibrated.epsilon,
        }
    if tr.realized_n and min(tr.realized_n) != max(tr.realized_n):
        out["realized_cohorts"] = {
            "min": min(tr.realized_n), "max": max(tr.realized_n),
            "mean": sum(tr.realized_n) / len(tr.realized_n),
        }
    tr.tracker.close()
    per_round = mech.per_round_epsilon(fcfg.clients_per_round, 8.0)
    if per_round > 0:
        out["per_round_eps_alpha8"] = per_round
        out["rdp_eps_alpha8"] = tr.accountant.rdp_epsilon(8.0)
        eps, alpha = tr.accountant.dp_epsilon(1e-5)
        out["dp_eps_at_1e-5"] = eps
        out["dp_alpha"] = alpha
        print(f"[{mech.name}] total RDP eps(alpha=8) = {out['rdp_eps_alpha8']:.3f}; "
              f"(eps, delta=1e-5)-DP eps = {eps:.3f} via alpha={alpha}")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=300)
    ap.add_argument("--clients", type=int, default=3400)
    ap.add_argument("--per-round", type=int, default=40)
    ap.add_argument("--lr", type=float, default=1.0)
    ap.add_argument("--clip", type=float, default=0.02)
    ap.add_argument("--m", type=int, default=16)
    ap.add_argument("--q", type=float, default=0.42)
    ap.add_argument("--delta-ratio", type=float, default=1.0)
    ap.add_argument("--theta", type=float, default=0.25)
    ap.add_argument("--r", type=float, default=0.6)
    ap.add_argument("--mechanism", default="all",
                    help="'all', a registered name "
                         f"({', '.join(mechanism_names())}), or a "
                         "'name:k=v,...' spec string; the flags above act "
                         "as defaults for whatever the spec leaves unset")
    ap.add_argument("--engine", default="scan",
                    help="round engine: a registered name "
                         f"({', '.join(engine_names())}) or a "
                         "'name:k=v,...' spec string, e.g. "
                         "'async:cadence=16,max_staleness=4' "
                         "(docs/engines.md, docs/async.md): 'scan' = "
                         "device-resident jitted blocks (fastest on one "
                         "device), 'shard' = scan blocks sharded over all "
                         "visible devices with encoded-domain cross-shard "
                         "aggregation (docs/scaling.md), 'perround' = "
                         "same step driven per round, 'host' = legacy "
                         "host loop, 'async' = traffic-shaped buffered "
                         "aggregation")
    ap.add_argument("--server-opt", default="sgd",
                    help="server optimizer at the decode-then-apply "
                         "boundary: 'sgd' (the paper's w - lr*g_hat), "
                         "'momentum', or 'adam'; state rides the jitted "
                         "carry and checkpoints with the params")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory (per-mechanism subdirs); "
                         "enables --ckpt-every and --resume")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="checkpoint every N rounds (requires --ckpt-dir)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest checkpoint from --ckpt-dir "
                         "and continue BIT-IDENTICALLY to the "
                         "uninterrupted run (params + epsilon sequence)")
    ap.add_argument("--shards", type=int, default=None,
                    help="engine=shard: cohort shards (default: all devices)")
    ap.add_argument("--staging", default="full", choices=["full", "stream"],
                    help="engine=shard: 'stream' stages only each block's "
                         "active cohort (bounded memory for huge "
                         "populations)")
    ap.add_argument("--fused-rounds", action="store_true",
                    help="stream the round's clip->encode->sum through the "
                         "fused kernel (docs/kernels.md): never materializes "
                         "the (cohort, dim) encoded batch, bit-identical "
                         "results (scan/perround/shard engines)")
    ap.add_argument("--subsampling", default="fixed",
                    choices=["fixed", "poisson"],
                    help="cohort realization: 'poisson' includes each "
                         "client i.i.d. at rate per_round/clients; the "
                         "accountant composes each round at its REALIZED "
                         "cohort size (docs/privacy.md)")
    ap.add_argument("--dropout", type=float, default=0.0,
                    help="i.i.d. per-selected-client dropout probability; "
                         "survivors are what the round is accounted at")
    ap.add_argument("--target-eps", type=float, default=None,
                    help="calibrate each private mechanism family to this "
                         "total (eps, --target-delta)-DP budget over "
                         "--rounds rounds (privacy knobs --q/--theta/--r "
                         "are then solved for, and the trainer halts at "
                         "budget exhaustion)")
    ap.add_argument("--target-delta", type=float, default=1e-5)
    ap.add_argument("--track", default=None,
                    help="tracker spec (make_mechanism-style, "
                         "docs/telemetry.md): 'json:runs/fl.json', "
                         "'csv:runs/fl.csv', or a '+'-joined composite; "
                         "per-round eps/accuracy series land there. With "
                         "--mechanism all, each mechanism writes its own "
                         "suffixed file (like the checkpoint subdirs)")
    ap.add_argument("--out", default=None, help="write results JSON")
    args = ap.parse_args()
    enable_compile_cache()
    if args.resume and not args.ckpt_dir:
        ap.error("--resume requires --ckpt-dir")

    fcfg = FedConfig(
        num_clients=args.clients, clients_per_round=args.per_round,
        rounds=args.rounds, lr=args.lr, eval_size=1000,
        data_noise=1.5, data_deform=1.2,  # see benchmarks/fig3_fl_emnist.py
        engine=args.engine, shards=args.shards, staging=args.staging,
        fused_rounds=args.fused_rounds,
        server_opt=args.server_opt,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        subsampling=args.subsampling, dropout=args.dropout,
        budget_eps=args.target_eps, budget_delta=args.target_delta,
        # budget mode: account on the same alpha grid calibration optimizes
        # over, so the run can afford exactly the calibrated round count
        accountant_alphas=(tuple(DEFAULT_ALPHAS) if args.target_eps is not None
                           else FedConfig.accountant_alphas),
    )
    specs = (["none", "rqm", "pbm", "qmgeo"] if args.mechanism == "all"
             else [args.mechanism])
    defaults = dict(c=args.clip, m=args.m, q=args.q,
                    delta_ratio=args.delta_ratio, theta=args.theta, r=args.r)
    results = [run_one(s, fcfg, target_eps=args.target_eps,
                       resume=args.resume, track=args.track,
                       multi=len(specs) > 1, **defaults)
               for s in specs]
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2)
        print("wrote", args.out)


if __name__ == "__main__":
    main()
