"""The points a ``fig7_locality`` request must answer, as the benchmark
reckons them: every stride of the request in each of the two windows
(8 KiB and 256 MiB) that holds it at the request's burst (Shuhai Fig. 7;
a stride below the burst or above the window is no RST stream)."""

WINDOWS = (8 * 1024, 256 * 1024 * 1024)


def points(request: dict, config: dict) -> list:
    n = int(request["n"])
    return [{"key": (w, b, s), "n": n, "b": b, "s": s, "w": w, "a": 0,
             "engines": 1, "op": "read"}
            for w in WINDOWS for b in request["bursts"]
            for s in request["strides"] if b <= s <= w]


def served(result, pts: list) -> dict:
    """The GB/s the answer gives for each point; a missing point is left
    out, so the caller sees it missing."""
    out = {}
    for pt in pts:
        w, b, s = pt["key"]
        value = result.get(w, {}).get(b, {}).get(s)
        if value is not None:
            out[pt["key"]] = value
    return out
