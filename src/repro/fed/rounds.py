"""The jitted Algorithm-1 round-step and block builders.

One round, identical under every engine: split the round key; sample the
cohort slate; per-client clipped gradient (or FedAvg delta) over the
slate; one fused clip->encode over the (clients, dim) stack; mask
non-participants; SecAgg-sum the integer messages; decode g_hat at the
realized cohort size; route g_hat through the SERVER OPTIMIZER at the
decode-then-apply boundary (``repro.optim.Optimizer`` — plain SGD is the
paper's w - lr*g_hat, bit-identical by construction; momentum/adam carry
their state through the scan/shard carry, donated with the parameters).

``FedConfig.fused_rounds`` collapses the encode / mask / sum triple into
the mechanism's ``quantize_sum_batch`` (one streamed reduction, no
materialized encoded batch — kernels/fused_round_kernel.py) and, for
plain-SGD grid mechanisms, the decode / apply pair into
``decode_apply_sum`` — both bit-identical to the unfused sequence (see
``use_fused_apply`` and docs/kernels.md).

The trailing optimization_barrier pins the round boundary: XLA cannot
fuse one round's float math into the next, so the body compiles to the
same numerics whether it stands alone (perround) or is repeated inside an
unrolled scan block — the bit-for-bit parity the engine tests assert on
CPU. (Without it, cross-round fusion and while-loop single-threading on
XLA:CPU shift gradients by ~1 ULP, which RQM's randomized rounding then
amplifies.)

Each stage of a round is a device scope (``jax.named_scope``), which the
compiler keeps in the ``op_name`` of every op it emits, so a device trace
splits the round by stage: ``fed.grads`` (the cohort's gradient map; its
own ops stack the gradients), ``fed.client_grad`` (one client's gradient
and clip), ``fed.encode_sum`` (encode and participation mask; with fused
rounds also the sum), ``fed.secure_sum`` (the cohort sum and the
cross-shard SecAgg exchange) and ``fed.decode_apply`` (decode and the
server step). The key split, the slate draw and the gather of the
cohort's data lie outside them.

Builders return traced-side callables; the engine classes
(``repro.fed.engines``) own jit/shard_map wrapping and dispatch.
"""
from __future__ import annotations

import jax
import jax.flatten_util
import jax.numpy as jnp

from repro.core import secagg, wire
from repro.core.grid import GridGeometry
from repro.fed import cohort
from repro.kernels.decode_apply_kernel import decode_apply_sum


def index_batch(data, ids):
    """Select client rows out of a staged data pytree: every leaf has the
    clients axis leading, so a round's cohort batch is one gather per
    leaf. The engines treat batches as OPAQUE — only the task looks
    inside (fed/tasks.py)."""
    return jax.tree_util.tree_map(lambda a: a[ids], data)


def use_fused_apply(mech, cfg) -> bool:
    """True when the fused decode->optimizer-apply kernel may replace the
    decode_sum -> server-optimizer sequence bit-identically: fused rounds
    on, plain SGD (weight_decay would add a term the fused kernel does not
    carry), and a shared-affine-grid mechanism (GridGeometry params —
    RQM/QMGeo; PBM's decode is binomial-centered, 'none' decodes floats).
    Everything else falls back to the mechanism decode + optimizer step."""
    wd = (cfg.server_opt_options or {}).get("weight_decay", 0.0)
    return (cfg.fused_rounds and cfg.server_opt == "sgd" and not wd
            and isinstance(getattr(mech, "params", None), GridGeometry))


def hot_path_pack_bits(mech, cfg, slate) -> int | None:
    """The wire width (bits per packed field) of the fused hot path, or
    None when the round travels dense.

    Packing engages only where BOTH endpoints are fused — the packed
    round-sum kernel emits wire words and ``decode_apply_sum`` consumes
    them, so the dense (dim,) int32 sum never exists between them. That
    means: ``fused_rounds`` on, the fused decode->apply applicable
    (``use_fused_apply``), the cohort sum bound field-safe
    (``wire.packable`` over the worst case — the full slate), and the
    ``wire_packed`` knob not opted out. ``wire_packed=True`` forces the
    issue: raises (actionably) when the hot path or the bound cannot
    support packing instead of silently going dense."""
    if cfg.wire_packed is False:
        return None
    if not use_fused_apply(mech, cfg):
        if cfg.wire_packed:
            raise ValueError(
                "wire_packed=True requires the fused hot path it packs: "
                "fused_rounds=True, server_opt='sgd' with no weight_decay, "
                "and a shared-affine-grid mechanism (rqm/qmgeo). "
                "Drop wire_packed or enable the fused path."
            )
        return None
    bound = mech.sum_bound(slate)
    if cfg.wire_packed:
        return wire.check_packable(bound, where="wire_packed=True: ")
    return wire.sum_bits(bound) if wire.packable(bound) else None


def make_client_grad(mech, unravel, cfg, task, ctx=None):
    """Per-client release: the clipped gradient (local_steps=1, Algorithm
    1 exactly) or the clipped NEGATIVE model delta of several local SGD
    steps (FedAvg-RQM — the server's w - lr*g_hat then moves toward the
    clients' local optima). Same DP accounting either way: one [-c,c]^f
    vector per client per round.

    The objective comes from the TASK (fed/tasks.py): ``task.loss`` over
    an opaque batch pytree. When ``ctx`` carries a model axis (the shard
    engine's 2-D mesh, tp > 1), the gradient runs tensor-parallel —
    shard the global params, take the local grad of the task's 1/tp-
    corrected loss, then sync + all-gather back to the GLOBAL layout so
    the clipped vector (and hence the encode integers) is identical on
    every model shard."""
    local_steps, local_lr = cfg.local_steps, cfg.local_lr
    tp = int(getattr(ctx, "tp", 1) or 1) if ctx is not None else 1

    if tp > 1:
        def flat_grad(flat_params, batch):
            local = task.shard_params(unravel(flat_params), ctx)
            g_local = jax.grad(task.local_loss)(local, batch, ctx)
            g = task.gather_grads(g_local, ctx)
            gflat, _ = jax.flatten_util.ravel_pytree(g)
            return gflat
    else:
        def flat_grad(flat_params, batch):
            g = jax.grad(task.loss)(unravel(flat_params), batch)
            gflat, _ = jax.flatten_util.ravel_pytree(g)
            return gflat

    def client_grad(flat_params, batch):
        with jax.named_scope("fed.client_grad"):
            if local_steps <= 1:
                return jnp.clip(flat_grad(flat_params, batch),
                                -mech.clip, mech.clip)

            def body(flat, _):
                return flat - local_lr * flat_grad(flat, batch), None

            flat_new, _ = jax.lax.scan(body, flat_params, None,
                                       length=local_steps)
            delta = flat_params - flat_new
            return jnp.clip(delta, -mech.clip, mech.clip)

    return client_grad


def cohort_grads(client_grad, params, batch, *, per_client_params=False):
    """Every cohort member's ``client_grad``, one client at a time
    (``lax.map``): a client's gradient is then one fixed program whatever
    the cohort size or its split over chips. A vmap folds the clients
    into the batch of the convolutions and matmuls, and on a TPU the
    float rounding of each client's gradient then depends on how many
    clients share the call — enough to change the released integers
    between the shard engine's per-chip slice and the whole cohort.
    ``per_client_params``: ``params`` carries a leading client axis (the
    async engine's per-member parameter versions).

    Device scope ``fed.grads``; its own ops (outside each client's
    ``fed.client_grad``) write every gradient into the cohort's stack."""
    with jax.named_scope("fed.grads"):
        if per_client_params:
            return jax.lax.map(lambda pb: client_grad(*pb), (params, batch))
        return jax.lax.map(lambda b: client_grad(params, b), batch)


def make_server_apply(opt, cfg, hetero):
    """The decode-then-apply boundary: g_hat -> (new_params, new_state)
    via the pluggable server optimizer. Empty heterogeneous rounds (zero
    surviving participants) release nothing and move NOTHING — neither
    parameters nor optimizer state."""
    lr = cfg.lr

    def apply(flat, opt_state, g_hat, n_real):
        new, new_state = opt.update(g_hat, opt_state, flat, lr)
        if hetero:
            ok = n_real > 0
            new = jnp.where(ok, new, flat)
            new_state = jax.tree_util.tree_map(
                lambda a, b: jnp.where(ok, a, b), new_state, opt_state
            )
        return new, new_state

    return apply


def make_round_step(mech, cfg, opt, slate, client_grad):
    """The device-resident round step shared verbatim by the "perround"
    and "scan" engines (and, via the specialized 1-shard path, "shard").
    Carry is (flat, opt_state, key); also returns the round's encoded
    SecAgg sum and realized participant count for host-side accounting."""
    hetero = cohort.is_hetero(cfg)
    apply = make_server_apply(opt, cfg, hetero)
    fused = cfg.fused_rounds
    fused_apply = use_fused_apply(mech, cfg)
    pack_bits = hot_path_pack_bits(mech, cfg, slate)

    def round_step(flat, opt_state, key, data):
        key, k_sample, k_enc, k_drop = cohort.split_round_keys(cfg, key)
        ids, valid = cohort.sample_slate(cfg, slate, k_sample)
        grads = cohort_grads(client_grad, flat, index_batch(data, ids))
        # Shared clip->encode dispatch (clip is idempotent on the
        # already-clipped grads): one fused kernel call over the whole
        # (clients, dim) stack when the mechanism is kernel-backed. With
        # fused_rounds the encode and the SecAgg sum are ONE streamed
        # reduction — the (clients, dim) encoded batch never exists, and
        # the round has no fed.secure_sum scope; with pack_bits it leaves
        # the reduction already as b-bit wire words (core/wire.py) for the
        # packed decode_apply_sum to consume.
        with jax.named_scope("fed.encode_sum"):
            part = (cohort.participation(cfg, valid, k_drop) if hetero
                    else None)
            if fused:
                z_sum = mech.quantize_sum_batch(grads, k_enc, weights=part,
                                                pack_bits=pack_bits)
            else:
                z = mech.quantize_batch(grads, k_enc)
                if hetero:
                    z = z * part.astype(z.dtype)[:, None]  # non-participants
        if not fused:
            with jax.named_scope("fed.secure_sum"):
                z_sum = jnp.sum(z, axis=0, dtype=z.dtype)
        with jax.named_scope("fed.decode_apply"):
            if not hetero:
                n_real = jnp.int32(cfg.clients_per_round)
                n_dec = cfg.clients_per_round
            else:
                n_real = jnp.sum(part, dtype=jnp.int32)
                # an empty round releases nothing and moves nothing
                n_dec = jnp.maximum(n_real, 1)
            if fused_apply:
                new = decode_apply_sum(flat, z_sum, mech.params, n_dec,
                                       cfg.lr, pack_bits=pack_bits)
                new_state = opt_state
                if hetero:
                    new = jnp.where(n_real > 0, new, flat)
            else:
                g_hat = mech.decode_sum(z_sum, n_dec)
                new, new_state = apply(flat, opt_state, g_hat, n_real)
        new, new_state = jax.lax.optimization_barrier((new, new_state))
        if pack_bits is not None and cfg.collect_sums:
            # the collected observable stays the DENSE sum (exact unpack),
            # so the cross-engine / packed-vs-unpacked equality suites
            # compare like with like; without collect_sums the unpack is
            # dead code and never compiles into the round.
            z_sum = wire.unpack_bits(z_sum, pack_bits, flat.shape[0])
        return new, new_state, key, z_sum, n_real

    return round_step


def pick_unroll(cfg, length: int) -> int:
    """Full unroll ONLY on CPU, where XLA runs while-loop bodies
    single-threaded; TPU/GPU while loops lose nothing and unrolling would
    just bloat compile time and program size."""
    unroll = cfg.scan_unroll
    if unroll is None:
        unroll = length if jax.default_backend() == "cpu" else 1
    return min(unroll, length)


def make_block(round_step, cfg, *, streamed: bool = False):
    """A block of rounds as one ``lax.scan`` over the round step. With
    ``streamed`` staging the per-round cohort data rides the scan xs
    (leading axis = rounds); otherwise the staged population is closed
    over as a scan-invariant. Returns
    ``block(flat, opt_state, key, data, length)``."""
    hetero = cohort.is_hetero(cfg)
    collect = cfg.collect_sums

    def block(flat, opt_state, key, data, length):
        def body(carry, xs):
            f, s, k = carry
            b = xs if streamed else data
            f, s, k, z_sum, n_real = round_step(f, s, k, b)
            return (f, s, k), (z_sum if collect else None,
                               n_real if hetero else None)

        xs = data if streamed else None
        (flat, opt_state, key), (sums, ns) = jax.lax.scan(
            body, (flat, opt_state, key), xs, length=length,
            unroll=pick_unroll(cfg, length),
        )
        return flat, opt_state, key, sums, ns

    return block


def make_shard_round_step(mech, cfg, opt, slate, shards, client_grad):
    """The shard engine's round step (inside shard_map over ('shard',)).

    Identical key evolution to the scan engine: the key is replicated, so
    every shard derives the same k_sample/k_enc/k_drop and the same global
    cohort slate + masks. Each shard grads+encodes its slate/shards cohort
    slice (the row_offset keeps the RNG counters identical to the
    unsharded batch), takes its partial integer sum, and ONE cross-shard
    secure_sum of packed level indices crosses the shard boundary — never
    floats — before the replicated decode + server-optimizer step.

    On a 1-shard mesh the shard-local slice IS the whole cohort and the
    RNG row offset IS zero: both are specialized away statically so the
    round body traces to exactly the scan engine's program (the
    bit-identity contract for free, and none of the dynamic-slice /
    traced-offset overhead on single-device runs). Multi-shard meshes
    take the generic path.
    """
    hetero = cohort.is_hetero(cfg)
    apply = make_server_apply(opt, cfg, hetero)
    fused = cfg.fused_rounds
    fused_apply = use_fused_apply(mech, cfg)
    n = cfg.clients_per_round
    n_per = slate // shards
    bound = mech.sum_bound(slate)  # forced-packing safety checked at init
    prefer_packed = cfg.shard_packed is None or cfg.shard_packed
    pack_bits = hot_path_pack_bits(mech, cfg, slate)
    streamed = cfg.staging == "stream"
    multi = shards > 1

    def round_step(flat, opt_state, key, data):
        key, k_sample, k_enc, k_drop = cohort.split_round_keys(cfg, key)
        j = jax.lax.axis_index("shard") if multi else 0
        valid = None
        if streamed:
            # the block staging already gathered this round's slate in
            # sampled order and sharded it over the mesh; the device
            # re-derives only the (replicated) validity mask from the
            # same k_sample the host replayed.
            batch = data
            if hetero:
                _, valid = cohort.sample_slate(cfg, slate, k_sample)
        else:
            ids, valid = cohort.sample_slate(cfg, slate, k_sample)
            if multi:
                ids = jax.lax.dynamic_slice_in_dim(ids, j * n_per, n_per)
            batch = index_batch(data, ids)
        grads = cohort_grads(client_grad, flat, batch)
        with jax.named_scope("fed.encode_sum"):
            local = None
            if hetero:
                # replicated full-slate participation; each shard masks its
                # own row slice out of the partial sum
                part = cohort.participation(cfg, valid, k_drop)
                local = (jax.lax.dynamic_slice_in_dim(part, j * n_per, n_per)
                         if multi else part)
            if fused:
                # one streamed clip->encode->shard-local-sum: the per-shard
                # (n_per, dim) encoded slice is never materialized, and the
                # reduction the SecAgg boundary receives is already done —
                # with pack_bits, already as b-bit wire words.
                z_part = mech.quantize_sum_batch(
                    grads, k_enc, weights=local,
                    row_offset=j * n_per if multi else None,
                    total_rows=slate if multi else None,
                    pack_bits=pack_bits,
                )
            else:
                z = mech.quantize_batch(
                    grads, k_enc,
                    row_offset=j * n_per if multi else None,
                    total_rows=slate if multi else None,
                )
                if hetero:
                    z = z * local.astype(z.dtype)[:, None]
        # The SecAgg boundary. Packed hot path: the shard-local partials
        # are ALREADY minimal-width wire words, and int32 addition sums
        # their fields independently (field-safety checked against the
        # full-slate bound in hot_path_pack_bits), so one plain psum of
        # words IS the exact cross-shard SecAgg sum — at b bits per
        # coordinate on the interconnect. Dense path: integer level
        # indices, minimal-width-packed by secure_sum_bounded when the
        # bound allows (exact either way; the float 'none' baseline has
        # bound 0 and takes the plain psum).
        with jax.named_scope("fed.secure_sum"):
            if not fused:
                z_part = jnp.sum(z, axis=0, dtype=z.dtype)  # shard-local
            if pack_bits is not None:
                z_sum = jax.lax.psum(z_part, "shard")
            else:
                z_sum = secagg.secure_sum_bounded(
                    z_part, ("shard",), bound, packed=prefer_packed
                )
        with jax.named_scope("fed.decode_apply"):
            n_real = jnp.sum(part, dtype=jnp.int32) if hetero else jnp.int32(n)
            n_dec = jnp.maximum(n_real, 1) if hetero else n
            if fused_apply:
                new = decode_apply_sum(flat, z_sum, mech.params, n_dec,
                                       cfg.lr, pack_bits=pack_bits)
                new_state = opt_state
                if hetero:
                    new = jnp.where(n_real > 0, new, flat)
            else:
                g_hat = mech.decode_sum(z_sum, n_dec)
                new, new_state = apply(flat, opt_state, g_hat, n_real)
        new, new_state = jax.lax.optimization_barrier((new, new_state))
        if pack_bits is not None and cfg.collect_sums:
            # collected observable = the DENSE sum (exact unpack); dead
            # code unless collect_sums (see make_round_step)
            z_sum = wire.unpack_bits(z_sum, pack_bits, flat.shape[0])
        return new, new_state, key, z_sum, n_real

    return round_step
