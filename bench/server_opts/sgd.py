"""The reference's server step for ``server_opt`` ``sgd``: ``w - lr * g``,
with no state and no options."""


def init(flat):
    return ()


def apply(flat, state, g, *, lr):
    return flat - lr * g, state
