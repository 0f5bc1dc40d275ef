"""Requests completed in the window over the window's whole length."""


def read(run):
    done = sum(t.done and t.t_complete <= run.window_end for t in run.tickets)
    return done / run.window_s if done else None
