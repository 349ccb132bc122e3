"""Process start to the start of the window: imports, building and placing
the model, compiling or loading from the compile cache, warm-up steps."""


def read(run):
    return run.setup_s
