"""Plain reference of the federated round the benchmark times: Algorithm 1 of
arXiv:2306.11913, one client at a time, in straightforward ``jax.numpy``.
It imports nothing of the program.

One round, from the round key:

1. The cohort sampler named by the cell's ``subsampling``
   (``bench/cohorts/<name>.py``) draws the round's members and its encode
   key.
2. Each member takes the gradient of the reference loss on its own data at
   the current parameters and clips it as the mechanism states.
3. The mechanism named in the configuration (``bench/mechanisms/<name>.py``)
   encodes it at the member's place in the cohort's element
   order; the server sums the integers (SecAgg) and decodes the sum.
4. The server optimizer named by ``server_opt``
   (``bench/server_opts/<name>.py``) applies the decoded gradient.
"""
from __future__ import annotations

import functools

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np

from plugins import find


class Round:
    """The pieces one configuration and traffic mix name."""

    def __init__(self, fed: dict, mech: dict):
        self.fed = fed
        self.mech = find("mechanisms", mech["name"])
        self.params = {k: v for k, v in mech.items() if k != "name"}
        self.cohort = find("cohorts", fed.get("subsampling", "fixed"))
        self.opt = find("server_opts", fed.get("server_opt", "sgd"))
        self.opt_options = dict(fed.get("server_opt_options") or {},
                                lr=fed["lr"])

    def level_step(self, n: int) -> float:
        """How far one level of the released sum moves a parameter in the
        first step."""
        state = self.opt.init(0.0)
        one, _ = self.opt.apply(0.0, state, self.mech.level(n, **self.params),
                                **self.opt_options)
        nil, _ = self.opt.apply(0.0, state, 0.0, **self.opt_options)
        return abs(float(one) - float(nil))


def make_client_grad(loss, unravel, clip: float):
    @jax.jit
    def client_grad(flat, batch):
        g, _ = jax.flatten_util.ravel_pytree(jax.grad(loss)(unravel(flat),
                                                            batch))
        return jnp.clip(g, -clip, clip)

    return client_grad


def replay(loss, weights, data, rnd: Round, *, rounds: int, every: int, key):
    """The flat parameters after every ``every`` of ``rounds`` rounds from
    ``weights``, as a list. ``data``: the population's arrays with a
    leading client axis."""
    flat, unravel = jax.flatten_util.ravel_pytree(weights)
    dim = flat.shape[0]
    grad = make_client_grad(loss, unravel, rnd.mech.clip(**rnd.params))
    encode = functools.partial(rnd.mech.encode, **rnd.params)

    @functools.partial(jax.jit, static_argnames="n")
    def step(flat, state, z_sum, n):
        return rnd.opt.apply(flat, state,
                             rnd.mech.decode(z_sum, n, **rnd.params),
                             **rnd.opt_options)

    state = rnd.opt.init(flat)
    out = []
    for r in range(1, rounds + 1):
        key, k_enc, members = rnd.cohort.draw(key, rnd.fed)
        seed = rnd.mech.round_seed(k_enc)
        z_sum = jnp.zeros((dim,), jnp.int32)
        for row, cid in members:
            batch = {k: v[cid] for k, v in data.items()}
            z_sum = z_sum + encode(grad(flat, batch), seed,
                                   np.uint32((row * dim) % 2 ** 32))
        flat, state = step(flat, state, z_sum, len(members))
        if r % every == 0:
            out.append(flat)
    return out
