"""Layer: data build. Host seconds of the random-effect coordinates'
bucketing and placement in set-up, from the program's own
``photon.game.prepare.buckets`` and ``.place`` spans as the built fit hands
them back (``BuiltFit.prepare_seconds``), summed over the coordinates. A
program without that handle gives nothing to read."""


def read(run):
    return run["spans"].total("re_build") or None
