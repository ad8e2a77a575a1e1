"""Layer: compile. Backend compiles inside the measured window, from
``compile_watch``. Anything but 0 is a finding."""


def read(run):
    return run["compile"]["window"]["backend_compiles"]
