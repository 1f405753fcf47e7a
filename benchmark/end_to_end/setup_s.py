"""Process start to the first measured decision, s."""


def read(run):
    return run.setup["setup_s"]
