"""Token-assignments one held expert sees in one step, averaged over the
window's steps, expert layers and experts held (the program's
``moe.assignments_here`` counter). The deployment the configuration
stands for would send each expert ``chips sharing a layer`` times this
chip's tokens: how far the cell is from that load is this number against
the configuration's stated one."""


def read(ctx):
    counters = ctx.stamps.get("counters") or {}
    m = ctx.config.get("model", {}).get("config") or {}
    if not counters.get("moe.layer_steps"):
        return None
    held = m.get("experts_held") or m["n_routed_experts"]
    return counters["moe.assignments_here"] / (counters["moe.layer_steps"] * held)
