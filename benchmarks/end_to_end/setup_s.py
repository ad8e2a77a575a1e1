"""Process start to the first timed step: data made, built and placed,
programs compiled or read from the cache, the first steps driven."""


def read(run):
    return run["setup_s"]
