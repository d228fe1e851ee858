"""Share of the traced window in which no op ran on the first device the
cell uses: 1 - (union of its op intervals) / window, in percent."""


def read(rec):
    t = rec["trace"]
    if t is None:
        return None
    first = next(iter(t["devices"].values()))
    return 100.0 * (1.0 - first["busy_s"] / first["window_s"])
