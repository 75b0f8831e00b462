"""Seconds from the start of ``benchmark/run.py`` to the start of the
training worker's process: ``tpurun``, the standalone master, the
rendezvous and the spawn (the agent's own record splits it: ``agent_up``,
``rdzv``, ``spawn``). Where the worker was a warm spare, to the hand-off."""

from benchmark.startup_records import load


def read(ctx):
    start = load(ctx)
    if start is None or start.process_start is None:
        return None
    return start.process_start - ctx.run.t_start
