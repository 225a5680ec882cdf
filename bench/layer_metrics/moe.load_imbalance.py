"""How unevenly the routed rows fall on the experts held here: the
busiest held expert's rows, summed over dispatches and MoE layers
(``engine.moe.busiest_expert_tokens``), over the mean held expert's
(``engine.moe.routed_tokens`` over the number of held experts), both
changes over the traced span.  1.0 is an even load; the time of a
tick's expert layer follows its busiest expert."""


def read(run):
    routed = run.counters.get("engine.moe.routed_tokens")
    if not routed:
        return None
    busiest = run.counters["engine.moe.busiest_expert_tokens"]
    return run.shapes.experts_held * busiest / routed
