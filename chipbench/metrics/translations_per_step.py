"""Pages translated through the metadata engine (iRC probe and iRT walk,
the ``lookups`` counter) per decode step and layer, over the window."""


def read(v):
    d = v.counter_delta(("lookups",))
    return None if d is None else d / v.layer_steps()
