"""The generate unit's load seconds, from its ready line."""


def read(run):
    return run["ready"]["load_s"]
