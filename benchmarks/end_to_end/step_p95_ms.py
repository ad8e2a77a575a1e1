"""95th percentile of the wall of every step in the window, each closed by
the read-back of its result (host clock; a step here is ~0.25 s or more)."""
import statistics


def read(run):
    walls = sorted(s["wall_s"] for s in run["steps"])
    if len(walls) < 20:
        return None  # no 95th percentile of a handful
    return 1e3 * statistics.quantiles(walls, n=20, method="inclusive")[-1]
