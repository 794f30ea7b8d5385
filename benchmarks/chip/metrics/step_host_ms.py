"""step_host_ms: the engine's host time per working step, measured inside
the program: the seconds of its ``serve.step`` spans less those of the
``serve.prefill``, ``serve.decode`` and ``serve.verify`` spans inside them
(dispatch to synced result of the jitted calls), over the number of
``serve.step`` spans, in milliseconds.  ``host_gap_ms`` measured from the
engine's own spans.  It reads the engine's report (``spans``), which
covers the whole window and the drain, the traced part included; a
program without spans gives nothing."""


def read(run):
    spans = run.window.report.get("spans") or {}
    step = spans.get("serve.step")
    if not step or not step["count"]:
        return None
    jitted = sum(spans.get(n, {}).get("seconds", 0.0)
                 for n in ("serve.prefill", "serve.decode", "serve.verify"))
    return (step["seconds"] - jitted) / step["count"] * 1e3
