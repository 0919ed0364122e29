"""Share of the traced window in which no operation ran on the device."""


def read(run):
    t = run["trace"]
    if t["n_devices"] == 0 or not t["window_s"]:
        return None
    return 100.0 * t["idle_share"]
