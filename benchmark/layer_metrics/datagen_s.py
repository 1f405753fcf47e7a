"""The benchmark's clock around this part of set-up, s."""


def read(run):
    return run.setup.get("datagen_s")
