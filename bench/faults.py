"""Faults planted underneath the timed path, each one a way a round can go
wrong, to show that the comparison of ``correct.py`` catches it:

* ``unchanged_state``: the round step hands its parameters back unchanged;
* ``half_cohort``: half of each cohort is left out of the SecAgg sum and
  the decode takes the mean over the rest;
* ``altered_answer``: one client's encoded levels are altered where they
  are produced (mirrored against the largest level one client sends);
* ``no_exchange``: the SecAgg sum across chips is left out, so each chip
  decodes its own partial sum (a cell on several chips only).

The mechanism faults are planted in every registered mechanism's unfused
path (``quantize_batch``, ``decode_sum``). The round engines build their
programs when the trainer is made, so a fault is planted before that and
lifted after the run.
"""
from __future__ import annotations

import contextlib

FAULTS = ("unchanged_state", "half_cohort", "altered_answer", "no_exchange")


_MISSING = object()


@contextlib.contextmanager
def planted(fault: str):
    from repro.core import mechanisms, secagg
    from repro.fed import rounds

    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    classes = [mechanisms._REGISTRY[n] for n in mechanisms.mechanism_names()]
    saved = [(rounds, "make_round_step", rounds.make_round_step),
             (rounds, "make_shard_round_step", rounds.make_shard_round_step),
             (secagg, "secure_sum_bounded", secagg.secure_sum_bounded)]
    saved += [(cls, name, cls.__dict__.get(name, _MISSING)) for cls in classes
              for name in ("quantize_batch", "decode_sum")]

    def patch(name, wrap):
        """Wrap method ``name`` of every registered mechanism."""
        for cls in classes:
            setattr(cls, name, wrap(getattr(cls, name)))

    if fault == "unchanged_state":
        def unchanged(make_step):
            def make(*args, **kw):
                step = make_step(*args, **kw)

                def round_step(flat, opt_state, key, data):
                    _, _, key, z_sum, n = step(flat, opt_state, key, data)
                    return flat, opt_state, key, z_sum, n

                return round_step

            return make

        rounds.make_round_step = unchanged(saved[0][2])
        rounds.make_shard_round_step = unchanged(saved[1][2])
    elif fault == "half_cohort":
        def half_rows(quantize):
            def quantize_batch(self, g, key, **kw):
                return quantize(self, g, key, **kw)[: g.shape[0] // 2]
            return quantize_batch

        def half_n(decode):
            def decode_sum(self, z_sum, n):
                return decode(self, z_sum, n // 2)
            return decode_sum

        patch("quantize_batch", half_rows)
        patch("decode_sum", half_n)
    elif fault == "altered_answer":
        def altered(quantize):
            def quantize_batch(self, g, key, **kw):
                z = quantize(self, g, key, **kw)
                return z.at[0].set(self.sum_bound(1) - z[0])
            return quantize_batch

        patch("quantize_batch", altered)
    else:
        secagg.secure_sum_bounded = lambda z_part, *args, **kw: z_part
    try:
        yield
    finally:
        for owner, name, value in saved:
            if value is _MISSING:
                if name in owner.__dict__:
                    delattr(owner, name)
            else:
                setattr(owner, name, value)
