"""Device time per round and chip of the Pallas kernels
(``tpu_custom_call``): the RQM encode on the program's default path."""


def read(facts):
    t = facts["trace"]
    s = t["class_s"].get("pallas")
    return s / t["devices"] / facts["rounds"] * 1e3 if s else None
