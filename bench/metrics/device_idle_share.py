"""Share of the traced window in which no op ran on the device: one minus
the union of its ops' intervals over the window, averaged over chips."""


def read(facts):
    t = facts["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
