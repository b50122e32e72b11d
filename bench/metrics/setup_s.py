"""Process start to the first arrival: imports, weights, library upload,
warm-up and (in a cold checkout) compilation."""


def read(run):
    return run.setup_s
