"""Share of the costed drains' slot-rounds in which a slot did a
compression (%): the worker body's ``busy_slot_rounds`` over lanes times
slots times rounds, counted on the device in the window."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("slot_rounds"):
        return None
    return 100.0 * c["busy_slot_rounds"] / c["slot_rounds"]
