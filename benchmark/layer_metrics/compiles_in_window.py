"""Programs built inside the window: what ``setup_s`` exists to keep out
of it. Serving: ``compile.programs_n`` at the window's close less at its
opening. Training: the worker's ``compile_*`` records (one a program built
after its start-up record) whose building began inside the window's
cycles."""

from benchmark.startup_records import load


def read(ctx):
    start = load(ctx)
    if start is None:
        return None
    return start.compiles_in_window
