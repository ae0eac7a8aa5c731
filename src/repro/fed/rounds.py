"""The jitted Algorithm-1 round, written once, and the block builder.

One round, identical under every engine: split the round key; sample the
cohort slate; per-client clipped gradient (or FedAvg delta) over the
slate; one fused clip->encode over the (clients, dim) stack; mask
non-participants; SecAgg-sum the integer messages; decode g_hat at the
realized cohort size; route g_hat through the SERVER OPTIMIZER at the
decode-then-apply boundary (``repro.optim.Optimizer`` — plain SGD is the
paper's w - lr*g_hat, bit-identical by construction; momentum/adam carry
their state through the scan/shard carry, donated with the parameters).

The body is three stage functions that every jitted round calls:
``encode_sum`` (mask, clip->encode, the integer sum and, on a mesh, the
cross-shard exchange), ``decode_apply`` (decode, server step, empty-round
guard) and ``round_boundary``. ``make_round_step`` strings them together
for scan, perround and shard (``shards=S``); the async engine calls them
around its stale-parameter gather, discount and ring shift. A change to
the body therefore lands once, and every engine releases the same
integers. ``FedConfig.fused_rounds`` collapses encode / mask / sum into
the mechanism's ``quantize_sum_batch`` and, for plain-SGD grid
mechanisms, decode / apply into ``decode_apply_sum`` — both bit-identical
to the unfused sequence (``use_fused_apply``, docs/kernels.md).

``round_boundary``'s optimization_barrier pins the round boundary: XLA
cannot fuse one round's float math into the next, so the body compiles
to the same numerics whether it stands alone (perround) or is repeated
inside an unrolled scan block — the bit-for-bit parity the engine tests
assert on CPU. (Without it, cross-round fusion and while-loop
single-threading on XLA:CPU shift gradients by ~1 ULP, which RQM's
randomized rounding then amplifies.)

Each stage is a device scope (``jax.named_scope``), which the compiler
keeps in the ``op_name`` of every op it emits, so a device trace splits
the round by stage: ``fed.grads`` (the cohort's gradient map; its own
ops stack the gradients), ``fed.client_grad`` (one client's gradient and
clip), ``fed.encode_sum`` (mask and encode; with fused rounds also the
sum), ``fed.secure_sum`` (the cohort sum and the cross-shard exchange)
and ``fed.decode_apply`` (decode and the server step). The key split,
the slate draw and the gather of the cohort's data lie outside them.

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


def encode_sum(mech, cfg, grads, k_enc, valid=None, k_drop=None, *,
               pack_bits=None, shard=None, exchange=None):
    """Stage one: the (rows, dim) clipped gradient stack -> the integer
    SecAgg sum. Returns ``(z_sum, part)``.

    ``valid``: the slate's validity mask when the round masks its cohort
    (heterogeneous cohorts; the async engine's delivered rows), made the
    participation mask ``part`` here; None: all participate. ``shard=(j,
    total_rows)``: the stack is rows ``[j*n, (j+1)*n)`` of the slate —
    the shard masks its own slice, and the row offset keeps the RNG
    counters those of the whole batch. ``exchange``: the cross-shard sum
    of the shard-local partial. Fused rounds encode and sum in ONE
    streamed reduction (with ``pack_bits``, into b-bit wire words), so
    without an exchange there is no ``fed.secure_sum`` scope."""
    rows = {}
    with jax.named_scope("fed.encode_sum"):
        part = local = None
        if valid is not None:
            part = local = cohort.participation(cfg, valid, k_drop)
        if shard is not None:
            j, total_rows = shard
            n = grads.shape[0]
            if part is not None:
                local = jax.lax.dynamic_slice_in_dim(part, j * n, n)
            rows = dict(row_offset=j * n, total_rows=total_rows)
        if cfg.fused_rounds:
            z_sum = mech.quantize_sum_batch(grads, k_enc, weights=local,
                                            **rows, pack_bits=pack_bits)
        else:
            z = mech.quantize_batch(grads, k_enc, **rows)
            if local is not None:
                z = z * local.astype(z.dtype)[:, None]  # non-participants
    if cfg.fused_rounds and exchange is None:
        return z_sum, part
    with jax.named_scope("fed.secure_sum"):
        if not cfg.fused_rounds:
            z_sum = jnp.sum(z, axis=0, dtype=z.dtype)
        if exchange is not None:
            z_sum = exchange(z_sum)
    return z_sum, part


def decode_apply(mech, cfg, opt, flat, opt_state, z_sum, part, *,
                 fused=False, pack_bits=None, discount=None):
    """Stage two: decode the sum at the realized cohort size and take the
    server step. Returns ``(new, new_state, n_real)``. With ``part`` (from
    ``encode_sum``) an empty round releases nothing and moves NOTHING —
    neither parameters nor optimizer state. ``fused``:
    ``decode_apply_sum`` (``use_fused_apply``). ``discount``: a scalar on
    the decoded aggregate (the async staleness weight, post-processing)."""
    with jax.named_scope("fed.decode_apply"):
        if part is None:
            n_real = jnp.int32(cfg.clients_per_round)
            n_dec = cfg.clients_per_round
        else:
            n_real = jnp.sum(part, dtype=jnp.int32)
            n_dec = jnp.maximum(n_real, 1)
        if fused:
            new = decode_apply_sum(flat, z_sum, mech.params, n_dec, cfg.lr,
                                   pack_bits=pack_bits)
            new_state = opt_state
        else:
            g_hat = mech.decode_sum(z_sum, n_dec)
            if discount is not None:
                g_hat = g_hat * discount
            new, new_state = opt.update(g_hat, opt_state, flat, cfg.lr)
        if part is not None:
            ok = n_real > 0
            new = jnp.where(ok, new, flat)
            new_state = jax.tree_util.tree_map(
                lambda a, b: jnp.where(ok, a, b), new_state, opt_state)
    return new, new_state, n_real


def round_boundary(new, new_state, z_sum, cfg, pack_bits=None):
    """The barrier over the carried state; then, only when the run
    collects its sums, the packed sum's exact unpack, so the collected
    observable is always the DENSE sum (otherwise dead code)."""
    new, new_state = jax.lax.optimization_barrier((new, new_state))
    if pack_bits is not None and cfg.collect_sums:
        z_sum = wire.unpack_bits(z_sum, pack_bits, new.shape[-1])
    return new, new_state, z_sum


def make_round_step(mech, cfg, opt, slate, client_grad, *, shards=None,
                    streamed=False):
    """The synchronous engines' round step: carry (flat, opt_state, key),
    plus the round's SecAgg sum and realized count for accounting.

    ``shards=None``: one program over the whole cohort (scan, perround,
    async's plain corner). ``shards=S``: inside shard_map over
    ('shard',). The replicated key gives every shard the same slate and
    masks; each grads+encodes its slate/S slice, and one cross-shard sum
    of integer levels — never floats — precedes the replicated decode
    and step. On one shard the slice and row offset are specialized
    away: the scan program plus the exchange. ``streamed``: the block
    staging already gathered and sharded each round's slate."""
    hetero = cohort.is_hetero(cfg)
    fused_apply = use_fused_apply(mech, cfg)
    pack_bits = hot_path_pack_bits(mech, cfg, slate)
    multi = shards is not None and shards > 1
    n_per = slate // shards if multi else slate
    exchange = None
    if shards is not None:
        bound = mech.sum_bound(slate)  # forced-packing safety checked at init
        prefer_packed = cfg.shard_packed is None or cfg.shard_packed

        def exchange(z_part):
            # packed partials are wire words whose fields int32 addition
            # sums independently (field-safe at the full-slate bound), so
            # a plain psum IS the SecAgg sum; dense levels go through
            # secure_sum_bounded (packed when the bound allows)
            if pack_bits is not None:
                return jax.lax.psum(z_part, "shard")
            return secagg.secure_sum_bounded(z_part, ("shard",), bound,
                                             packed=prefer_packed)

    def round_step(flat, opt_state, key, data):
        key, k_sample, k_enc, k_drop = cohort.split_round_keys(cfg, key)
        j = jax.lax.axis_index("shard") if multi else None
        if streamed:
            batch, valid = data, None
            if hetero:  # re-derive the replicated validity mask
                _, valid = cohort.sample_slate(cfg, slate, k_sample)
        else:
            ids, valid = cohort.sample_slate(cfg, slate, k_sample)
            if multi:
                ids = jax.lax.dynamic_slice_in_dim(ids, j * n_per, n_per)
            batch = index_batch(data, ids)
        grads = cohort_grads(client_grad, flat, batch)
        z_sum, part = encode_sum(
            mech, cfg, grads, k_enc, valid if hetero else None, k_drop,
            pack_bits=pack_bits, shard=(j, slate) if multi else None,
            exchange=exchange)
        new, new_state, n_real = decode_apply(
            mech, cfg, opt, flat, opt_state, z_sum, part,
            fused=fused_apply, pack_bits=pack_bits)
        new, new_state, z_sum = round_boundary(new, new_state, z_sum, cfg,
                                               pack_bits)
        return new, new_state, key, z_sum, n_real

    return round_step


def make_shard_round_step(mech, cfg, opt, slate, shards, client_grad):
    """The shard engine's round step: ``make_round_step`` over ``shards``."""
    return make_round_step(mech, cfg, opt, slate, client_grad,
                           shards=shards, streamed=cfg.staging == "stream")


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
