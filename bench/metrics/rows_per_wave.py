"""SigSched: mean rows per executed wave in the window."""


def read(run):
    waves = [w for w in run.record.waves if w["lens"]]
    if not waves:
        return None
    return sum(len(w["lens"]) for w in waves) / len(waves)
