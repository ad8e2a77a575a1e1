"""Layer: random-effect programs. The share of the chip's HBM rate that the
random-effect sweep programs reach where the entities hold tens to
thousands of rows: work_game.py's bytes of the traced steps (per coordinate
and sweep, every bucket's padded block times the reads its slowest entity's
iterations need, plus one rescoring read of the flat rows) over the peak
bytes/s, over those programs' device time. The bound is BYTES: a 16-wide
per-entity logistic solve is under 1 flop a byte. Never 0: nothing where
nothing is read."""
from benchmarks.lib import trace


def read(run):
    seconds = trace.program_seconds(run["trace"], run["programs"].get("re_rows", []))
    per_step = run["block"].get("re_step_bytes")
    if not seconds or run["peaks"] is None or not per_step:
        return None
    nbytes = sum(per_step[len(per_step) - len(run["steps"]):])
    return (100.0 * nbytes / run["peaks"]["hbm_bytes_per_s"] / seconds) or None
