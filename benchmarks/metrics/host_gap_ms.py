"""Layer: host loop. Per traced step: the step's wall (its host annotation)
minus the device's busy union inside it; the mean over the traced steps."""


def read(run):
    tr = run["trace"]
    if not tr or not tr["steps"]:
        return None
    gaps = [s["wall_s"] - s["busy_s"] for s in tr["steps"]]
    return 1e3 * sum(gaps) / len(gaps)
