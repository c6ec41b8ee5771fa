"""Process start to the opening of the measured window: imports, weights
made on the device, the engine's cache, compilation (or the compile
cache's load) and the warm-up. Host clock."""


def read(run):
    return run.setup_s
