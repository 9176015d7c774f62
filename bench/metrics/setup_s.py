"""From the start of the benchmark's process to the start of the window:
JAX start-up, records and traffic made from the seed, every insert, and
the warm-up of each shape the traffic uses."""


def read(run):
    return run.setup_s
