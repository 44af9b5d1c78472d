"""The points a ``grid_cross_product`` request must answer, as the
benchmark reckons them: every address-mapping policy of the configuration
times the request's strides, directions, engine counts, arbitrations and
placements, at the configuration's smallest burst (Shuhai Sec. V-VI)."""
import itertools


def points(request: dict, config: dict) -> list:
    b = config["memory"]["min_burst"]
    n, w = int(request["n"]), int(request["w"])
    out = []
    for pol, s, op, eng, (arb, bb), plc in itertools.product(
            config["policies"]["table"], request["strides"], request["ops"],
            request["engines"], request["arbitrations"],
            request["placements"]):
        out.append({"key": (pol, s, op, eng, arb, bb, plc), "n": n, "b": b,
                    "s": s, "w": w, "a": 0, "policy": pol, "op": op,
                    "engines": eng, "arbitration": arb, "burst_beats": bb,
                    "placement": plc})
    return out


def served(result, pts: list) -> dict:
    gbps = result.get("gbps", {})
    return {pt["key"]: gbps[pt["key"]] for pt in pts if pt["key"] in gbps}
