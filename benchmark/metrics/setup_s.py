"""Set-up: process start to the window's first due arrival (s). Loading,
JAX start-up, the pool's restore and the warm-up through the served path,
compilation included where the compile cache is cold."""


def read(run):
    return run.setup_s
