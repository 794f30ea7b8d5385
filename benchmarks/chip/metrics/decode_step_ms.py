"""decode_step_ms: the program's seconds from dispatch to synced result
of a decode step, mean over the window's decode steps (outside the traced
part)."""


def read(run):
    xs = [s.decode for s in run.busy_steps() if s.decode > 0]
    return sum(xs) / len(xs) * 1e3 if xs else None
