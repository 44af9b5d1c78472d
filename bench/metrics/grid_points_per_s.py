"""grid_points_per_s: points answered in the window over the window's
seconds (host clock)."""


def read(run):
    points = sum(r.answer["points"] for r in run.records)
    return points / run.window_s if points else None
