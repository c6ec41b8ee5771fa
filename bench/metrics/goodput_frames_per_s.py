"""Frames that finished within their deadline (their camera's frame
period, from the due time), over the window's seconds. Host clock."""


def read(run):
    met = sum(r.finished and r.latency_s <= r.frame.deadline_s
              for r in run.records)
    return met / run.seconds
