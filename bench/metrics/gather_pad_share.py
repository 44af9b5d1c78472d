"""gather_pad_share: the share of the gather engine's grid steps that read
no block (steps past each call's count, its grid rounded up to a bucket),
over the window's requests, in %: 100 x sum(pad_steps) / sum(grid_steps),
the counters each answer carries."""


def read(run):
    grid = sum(r.answer.get("grid_steps", 0) for r in run.records)
    if not grid:
        return None
    return 100.0 * sum(r.answer.get("pad_steps", 0)
                       for r in run.records) / grid
