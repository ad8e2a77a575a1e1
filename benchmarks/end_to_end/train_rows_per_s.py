"""Training rows x feature passes completed over the whole window, over the
whole window's seconds: the rows the solves streamed through their kernels
each second. The window ends with the step in flight at ``--seconds``: its
work and its time both count.

The passes are those the steps NEEDED, counted in ``lib/work.py`` from each
step's iterations, line-search trials and Hessian-vector products: not a
pass counter the program keeps, and not the reads one implementation makes.
It is a rate of the kernels: a change that brings a solve to its end in fewer
passes does not move it (PERF.md says where that would show). The unit is the
pass and not the optimizer iteration because an iteration's work depends on
the seed (trials, CG steps) and a pass's does not."""


def read(run):
    passes = sum(s["passes"] for s in run["steps"])
    return run["rows"] * passes / run["window_s"]
