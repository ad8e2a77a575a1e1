"""Layer: compile. Seconds of the backend compiles of set-up that the
persistent cache did not serve: the part of ``compile_s`` a cache policy
could remove. From the program's ``compile_watch`` table, as
``uncached_compiles`` reads it."""
from benchmarks.metrics.uncached_compiles import setup_programs


def read(run):
    rows = setup_programs(run)
    if rows is None:
        return None
    return sum(r["backend_compile_s"] - r["cache_served_s"] for r in rows.values())
