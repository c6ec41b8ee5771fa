"""Share of the engine's read-backs of program tokens made while a later
program was already dispatched, so the device had work while the host
waited, from the engine's ``report()["pipeline"]``, in percent. Silent
where the program keeps no such counters or read nothing. Program
counter."""


def read(run):
    pipe = run.report.get("pipeline")
    if not pipe or not pipe["readbacks"]:
        return None
    return 100.0 * pipe["overlapped"] / pipe["readbacks"]
