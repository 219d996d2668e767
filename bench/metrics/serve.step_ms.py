"""serve.step_ms: the benchmark's own span around ``ServeLoop.run_step``,
total over the steps that served requests, per step."""


def read(ctx):
    spans = getattr(ctx, "step_spans", None)
    if not spans:
        return None
    return sum(e - s for s, e in spans) / len(spans) * 1e3
