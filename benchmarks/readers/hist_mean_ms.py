"""Mean of one of the program's histograms over the measured window, in ms.

The program's histograms keep a sum and a count per series (and coarse
buckets, too coarse for a median); the runner hands over the difference
between the window's end and its start.
"""


def read(data: dict, *, histogram: str) -> float | None:
    cell = data.get("hist", {}).get(histogram)
    if not cell or cell["count"] <= 0:
        return None
    return cell["sum"] / cell["count"] * 1e3
