"""Layer: kernels. The share of the chip's HBM rate that the feature passes
of the fixed-effect solve reach: work.py's bytes of one pass (sparse 8 B a
nonzero, dense itemsize x n x d) times the passes the traced steps needed
(work.py, from iterations, trials and Hessian-vector products), over the peak bytes/s, over the solve
programs' device time. The bound is BYTES: these passes are ~2 flop a byte."""
from benchmarks.lib import trace, work


def read(run):
    fe = trace.program_seconds(run["trace"], run["programs"].get("fe_solve", []))
    if fe is None or run["peaks"] is None:
        return None
    passes = sum(s["passes"] for s in run["steps"])
    _, nbytes = work.step_work([(run["block"], passes)])
    return 100.0 * nbytes / run["peaks"]["hbm_bytes_per_s"] / fe
