"""Set-up: process start to the first due request (weights made from the
seed, engine built, every program of the window loaded and run once)."""


def read(run):
    return run.setup_s
