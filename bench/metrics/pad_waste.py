"""Bucketing: padded samples over stacked samples of the waves executed
in the window, in percent."""


def read(run):
    stacked = sum(w["bucket"] * len(w["lens"]) for w in run.record.waves)
    if not stacked:
        return None
    used = sum(sum(w["lens"]) for w in run.record.waves)
    return 100.0 * (stacked - used) / stacked
