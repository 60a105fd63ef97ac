"""Process start to the first due request: build or load, upload,
warm-up and compiles."""


def read(run):
    return run.setup_s
