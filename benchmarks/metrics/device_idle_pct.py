"""Layer: device. One minus the device's busy union over the traced
window."""


def read(run):
    tr = run["trace"]
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
