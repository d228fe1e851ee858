"""Peak device memory of the fullest chip, in GB: ``peak_bytes_in_use``
plus ``peak_bytes_reserved`` (this runtime books the step programs'
temporaries under ``reserved``)."""


def read(rec):
    peaks = [m.get("peak_bytes_in_use", 0) + m.get("peak_bytes_reserved", 0)
             for m in rec["memory"]]
    return max(peaks) / 1e9 if peaks and max(peaks) > 0 else None
