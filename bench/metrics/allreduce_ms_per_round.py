"""Device time per round and chip of the cross-chip collectives: the
SecAgg sum of the shard engine."""


def read(facts):
    t = facts["trace"]
    s = t["class_s"].get("collective")
    return s / t["devices"] / facts["rounds"] * 1e3 if s else None
