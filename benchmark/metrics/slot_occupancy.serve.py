"""Mean share of the slot grid that was decoding, over the window's
ticks (``ServingMetrics.slot_occupancy``)."""


def read(run):
    if run["kind"] != "decode" or not run["ticks"]:
        return None
    return 100.0 * run["slot_occupancy"]
