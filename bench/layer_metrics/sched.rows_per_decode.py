"""Rows decoded per decode dispatch in the traced span: decoded tokens
over the change of the Engine's ``engine.decode.steps`` counter."""


def read(run):
    steps = run.counters.get("engine.decode.steps")
    decoded = sum(len(d.valid) for d in run.dispatches if d.kind == "decode")
    return decoded / steps if steps else None
