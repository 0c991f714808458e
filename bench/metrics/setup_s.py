"""Process start to window start: everything before the measured window."""


def read(run):
    return run["setup"]["setup_s"]
