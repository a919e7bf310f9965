"""Mean live slots over slots, per decode step of the window (the live
slots as the serve loop hands them to its decode forward)."""


def read(run):
    win = [len(d.rows) for d in run.clients.decodes if d.in_window]
    return 100.0 * sum(win) / len(win) / run.slots if win else None
