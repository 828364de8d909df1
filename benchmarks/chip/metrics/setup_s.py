"""Set-up: weights, zoo, engine and the warm-up stretch, up to the
window's start (host clock)."""


def read(run):
    return run.setup_s
