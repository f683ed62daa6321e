"""Process start to the start of the timed window, on the harness's clock."""


def read(run):
    return run["setup_s"]
