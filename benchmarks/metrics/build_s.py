"""Layer: data build. The benchmark's own span round making the data, the
program's host-side layout build and placement, closed by a read-back."""


def read(run):
    return run["spans"].total("build")
