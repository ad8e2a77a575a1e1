"""Layer: compile. Seconds of backend compiles (or of reading them back
from the persistent cache) during set-up, from the program's
``compile_watch`` (jax.monitoring events)."""


def read(run):
    return run["compile"]["setup"]["backend_compile_s"]
