"""Layer: compile. Programs that went through a real backend compile during
set-up: not served by the persistent cache. From the program's
``compile_watch`` table (one row per program name, always on once
``install()`` has run), for the part of the process before the window's
first ``step`` span; the spans and the table share ``perf_counter``.
A program without that table gives nothing to read."""


def setup_programs(run):
    """The table's rows for set-up alone: the whole process's less what
    compiled from the window's first step on. ``None`` where the program
    keeps no such table."""
    from photon_tpu.util import compile_watch

    if not hasattr(compile_watch, "programs_since"):
        return None
    starts = [s for name, s, _ in run["spans"].rows if name == "step"]
    window = starts[len(starts) - len(run["steps"]):] if run["steps"] else []
    rows = compile_watch.programs()
    later = compile_watch.programs_since(window[0]) if window else {}
    for name, after in later.items():
        for key, value in after.items():
            if key != "last_t":
                rows[name][key] -= value
    return rows


def read(run):
    rows = setup_programs(run)
    if rows is None:
        return None
    return sum(r["compiles"] - r["cache_served"] for r in rows.values())
