"""Layer: random-effect programs. Device time of the random-effect
coordinates' sweep programs per traced step where the entities hold tens to
thousands of rows (``glmix_movielens.sweeps``: every bucket's solves, whose
ROW axis carries the work, and the flat rescoring, both coordinates, every
sweep), by HLO module name in the trace."""
from benchmarks.lib import trace


def read(run):
    total = trace.program_seconds(run["trace"], run["programs"].get("re_rows", []))
    if total is None:
        return None
    return 1e3 * total / len(run["trace"]["steps"])
