"""Share of the prefill rows x width dispatched in the traced span that
held prompt tokens: the change of the Engine's ``engine.prefill.tokens``
over that of ``engine.prefill.dispatched_tokens``."""


def read(run):
    dispatched = run.counters.get("engine.prefill.dispatched_tokens")
    if not dispatched:
        return None
    return 100.0 * run.counters["engine.prefill.tokens"] / dispatched
