"""Training tokens fed to an oracle call in the window, over the window's
wall time, for all chips of the cell (eval tokens do not count)."""


def read(run):
    return run.work["tokens"] * run.window.rounds / run.window.window_s
