"""Layer: random-effect programs. Device time of the random-effect
coordinates' sweep programs (every bucket's solves and the rescoring, both
coordinates) per traced step, by HLO module name in the trace."""
from benchmarks.lib import trace


def read(run):
    total = trace.program_seconds(run["trace"], run["programs"].get("re_solve", []))
    if total is None:
        return None
    return 1e3 * total / len(run["trace"]["steps"])
