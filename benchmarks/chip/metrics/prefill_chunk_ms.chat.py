"""prefill_chunk_ms.chat: the program's seconds from dispatch to synced
result of a prefill chunk, mean over the window's chunks (outside the
traced part), in the cells judged on time to first token."""


def read(run):
    xs = [s.prefill for s in run.busy_steps() if s.prefill > 0]
    return sum(xs) / len(xs) * 1e3 if xs else None
