"""The longest Python garbage collection of the process since the engine's
counters were last reset (at the end of the warm-up), from the engine's
``report()["host"]``, in ms; 0 where none ran. Silent where the program
keeps no such counters. Program counter."""


def read(run):
    host = run.report.get("host")
    return None if host is None else 1e3 * host["gc_pause_max_s"]
