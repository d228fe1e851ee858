"""Share of the traced window, on the first device the cell uses, in
which a collective op runs and no other op does, in percent.  Nothing to
read (``None``) where the trace holds no collective."""


def read(rec):
    t = rec["trace"]
    if t is None:
        return None
    first = next(iter(t["devices"].values()))
    if first["collective_s"] <= 0.0:
        return None
    return 100.0 * first["collective_exposed_s"] / first["window_s"]
