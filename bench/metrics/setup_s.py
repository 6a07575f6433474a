"""setup_s: from the start of the process to the opening of the window
(weights, server, compiles or cache loads, warm-up, set-up prefill)."""


def read(run):
    return run.setup_s
