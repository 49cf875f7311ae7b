"""Process start to the first timed request, compilation included."""


def read(run):
    return run.setup_s
