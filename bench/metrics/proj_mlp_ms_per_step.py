"""Device time of the step's projections and MLP (the ``proj`` and
``mlp`` named scopes) per batch-step, in ms; nothing where the trace
carries no scopes."""


def read(summary, run):
    scope_s = getattr(summary, "scope_s", None) or {}
    s = scope_s.get("proj", 0.0) + scope_s.get("mlp", 0.0)
    if not s or not run["batch_steps"]:
        return None
    return 1e3 * s / run["chips"] / run["batch_steps"]
