"""Mean share of the engine's slots in use per decode step, as the
engine's own ``report()`` counts it, in percent. Program counter."""


def read(run):
    occ = run.report.get("slot_occupancy")
    return None if not occ else 100.0 * occ
