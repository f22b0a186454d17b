"""Seconds from process start to the start of the measured window:
imports, weights, engine, the warm-up of every shape and the warm-up
phase of the cell's own traffic."""


def read(run):
    return run.setup_s
