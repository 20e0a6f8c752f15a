"""setup_s: process start to the window's start (graph build, plan
compile, warm-up of every shape the window uses; compiles in a cold run)."""


def read(run):
    return run.setup_s
