"""The generate unit's warm seconds, from its ready line."""


def read(run):
    return run["ready"]["warm_s"]
