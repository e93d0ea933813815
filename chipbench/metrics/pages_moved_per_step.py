"""Pages promoted plus pages demoted per decode step and layer, over the
window (the tiered backend's ``migrations`` and ``demotions`` counters,
read from device state at the window's open and close)."""


def read(v):
    d = v.counter_delta(("migrations", "demotions"))
    return None if d is None else d / v.layer_steps()
