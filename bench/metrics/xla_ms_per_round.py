"""Device time per round and chip of the ops XLA emitted itself (neither a
Pallas kernel nor a collective): the client gradients, the XLA sum, the
decode and the update."""


def read(facts):
    t = facts["trace"]
    s = t["class_s"].get("other")
    return s / t["devices"] / facts["rounds"] * 1e3 if s else None
