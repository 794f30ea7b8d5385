"""decode_commit_ms: the engine's host work after each decode step's
tokens are back (its ``serve.commit`` span: the step's diagnostics pulled
to the host, load and residency observation, KV occupancy and the
per-slot token loop with each finished request), mean over the decode
and verify steps, in milliseconds.  It reads the engine's report
(``spans``), which covers the whole window and the drain, the traced
part included; a program without spans gives nothing."""


def read(run):
    spans = run.window.report.get("spans") or {}
    commit = spans.get("serve.commit")
    steps = sum(spans.get(n, {}).get("count", 0)
                for n in ("serve.decode", "serve.verify"))
    if not commit or not steps:
        return None
    return commit["seconds"] / steps * 1e3
