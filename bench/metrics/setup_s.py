"""Process start to the window's opening: imports, device, weights,
server, and every program the window runs compiled or loaded (host
clock)."""


def read(run):
    return run.setup_s
