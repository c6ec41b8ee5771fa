"""The engine's own host work per step (scheduling, bookkeeping,
retirement: ``step()``'s wall time less its program launches and its waits
for tokens), from the engine's ``report()["host"]``, in ms. Silent where
the program keeps no such counters. Program counter."""


def read(run):
    host = run.report.get("host")
    if not host or not host["steps"]:
        return None
    return 1e3 * host["host_s"] / host["steps"]
