"""Mean host milliseconds per step on TrainLoop's between-step control
path (the poll before the step; Reshape's observe and re-plan, the expert
migration's dispatch and the plan update after it), from the
``t_control_s`` of each window step's history entry.  None where the loop
records no such time."""


def read(run):
    hist = run.get("history") or []
    if not hist or any("t_control_s" not in h for h in hist):
        return None
    return 1e3 * sum(h["t_control_s"] for h in hist) / len(hist)
