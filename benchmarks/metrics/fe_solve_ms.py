"""Layer: optimizer programs. Device time of the fixed-effect solve's
programs per traced step, by HLO module name in the trace."""
from benchmarks.lib import trace


def read(run):
    total = trace.program_seconds(run["trace"], run["programs"].get("fe_solve", []))
    if total is None:
        return None
    return 1e3 * total / len(run["trace"]["steps"])
