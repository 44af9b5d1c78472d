"""Bytes an RST stream moves, reckoned from its parameters alone.

One engine issues ``n`` transactions of ``B`` bytes; ``engines`` engines
move that each, and a duplex point moves it in both directions.  This is
the work whatever implements it, so it never comes from the program.
"""


def stream_bytes(point: dict) -> int:
    directions = 2 if point.get("op", "read") == "duplex" else 1
    return (int(point["n"]) * int(point["b"]) * int(point.get("engines", 1))
            * directions)
