"""Mean share of the engine's slots that were active, over the window's
steps (``stats()["slot_occupancy"]`` after each ``step()``)."""


def read(run):
    occ = [s[0] for s in run.get("samples", ())]
    if not occ:
        return None
    return 100.0 * sum(occ) / len(occ) / run["slots"]
