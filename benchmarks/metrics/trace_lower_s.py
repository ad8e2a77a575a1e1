"""Layer: compile. Seconds of jaxpr tracing and MLIR lowering during
set-up, summed over programs: the host's share of a warm start, which
``compile_s`` does not hold. A traced function that calls jitted ones
counts their tracing in its own row and in theirs, as ``compile_watch``'s
``trace_s`` total does. From its table, as ``uncached_compiles`` reads it."""
from benchmarks.metrics.uncached_compiles import setup_programs


def read(run):
    rows = setup_programs(run)
    if rows is None:
        return None
    return sum(r["trace_s"] + r["lowering_s"] for r in rows.values())
