"""The clip -> encode -> sum stage's share of its HBM roofline: the bytes
any implementation must move per round and chip (each cohort member's
float32 gradient read once, the int32 sum written once) at the chip's
peak bandwidth, over the Pallas kernels' device time per round."""


def read(facts):
    t = facts["trace"]
    s = t["class_s"].get("pallas")
    if not s:
        return None
    per_chip = facts["cohort"] // facts["chips"]
    need = (per_chip * facts["dim"] + facts["dim"]) * 4
    floor_s = need / facts["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / (s / t["devices"] / facts["rounds"])
