"""Layer: whole step. The least time the chip could take for ALL the work
of the traced steps (the larger of flops over peak FLOP/s and bytes over
peak bytes/s, from work.py's count of the passes they needed) over the steps'
wall. For these solves bytes bind."""
from benchmarks.lib import peaks, work


def read(run):
    if run["peaks"] is None or not run["trace"]:
        return None
    passes = sum(s["passes"] for s in run["steps"])
    flops, nbytes = work.step_work([(run["block"], passes)])
    least = peaks.least_seconds(flops, nbytes, run["device_kind"])
    return 100.0 * least / run["window_s"]
