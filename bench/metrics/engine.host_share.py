"""The engine's own host work (``report()["host"]["host_s"]``) over the run
from the window's opening to the end of the drain, in percent. The engine
blocks on every program's tokens, so the device idles through this time:
it reads against ``device.idle_share`` of the same traced run. Silent
where the program keeps no such counters. Program counter."""


def read(run):
    host = run.report.get("host")
    if not host or not run.records:
        return None
    r = run.records[0]
    opened = r.due_t - r.frame.due_s
    return 100.0 * host["host_s"] / (run.end_t - opened)
