"""The comparison that decides ``correct``: the parameters the timed path
produced in set-up, block by block over its first rounds (at least
``CHECK_ROUNDS``), against the plain reference's over the same rounds
from the same weights, data and round key. The reference follows its own
state, never the program's.

The numbers, each held under its own limit (``bench/limits/<cell>.json``
names the ones a cell compares):

* ``first_update_mismatch_share``: over the first block, the share of
  coordinates whose change differs from the reference's by half a level
  of the released sum or more. One level of the SecAgg sum moves a
  parameter by ``lr * 2 (c + delta) / (n (m - 1))``, so in a one-round
  block this counts, coordinate by coordinate, the released integers
  that differ.
* ``first_update_norm_gap``: over the first block, the largest gap, leaf
  by leaf, between the norm of the program's change and the norm of the
  reference's, relative to the larger of that leaf's reference norm and
  the median leaf's. In a one-round block the change is ``lr`` times the
  first decoded gradient as the optimizer gets it. RQM's noise moves every
  coordinate of every leaf, so no leaf is left out.
* ``total_update_norm_gap``: the same over all the rounds checked.

``leaf_mismatch`` (not compared) gives the first block's mismatch share
of each leaf, to show where a mismatch lies.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

CHECK_ROUNDS = 3


def _norm_gap(d_prog, d_ref, unravel) -> float:
    norm = lambda t: np.array([float(jnp.linalg.norm(x)) for x in
                               jax.tree_util.tree_leaves(unravel(t))])
    n_prog, n_ref = norm(d_prog), norm(d_ref)
    floor = np.maximum(n_ref, np.median(n_ref))
    return float(np.max(np.abs(n_prog - n_ref) / floor))


def numbers(flat0, prog: list, ref: list, unravel, level: float):
    """``(values, leaf_mismatch)`` from the program's and the reference's
    flat parameters at each checked block boundary."""
    first_p = jnp.asarray(prog[0]) - flat0
    first_r = ref[0] - flat0
    off = (jnp.abs(first_p - first_r) >= 0.5 * level).astype(jnp.float32)
    leaves = jax.tree_util.tree_leaves_with_path(unravel(off))
    leaf_mismatch = {jax.tree_util.keystr(k): float(jnp.mean(v))
                     for k, v in leaves}
    values = {
        "first_update_mismatch_share": float(jnp.mean(off)),
        "first_update_norm_gap": _norm_gap(first_p, first_r, unravel),
        "total_update_norm_gap": _norm_gap(jnp.asarray(prog[-1]) - flat0,
                                           ref[-1] - flat0, unravel),
    }
    return values, leaf_mismatch


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, compared)``: every number a cell compares at or under
    its limit."""
    compared = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    ok = all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in compared.values())
    return ok, compared
