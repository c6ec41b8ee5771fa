"""Share of the engine's program calls (prefills and decodes) that
consumed the slot cache handed to them, so updated it in place instead of
copying it, from the engine's ``report()["cache"]``, in percent. Silent
where the program keeps no such counters or made no call. Program
counter."""


def read(run):
    cache = run.report.get("cache")
    if not cache or not cache["calls"]:
        return None
    return 100.0 * cache["in_place"] / cache["calls"]
