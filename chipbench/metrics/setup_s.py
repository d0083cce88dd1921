"""Set-up: process start to the window's start (import, mesh, build,
compile or cache read, state init, the checked and warm-up steps)."""


def read(run):
    return run.setup_s
