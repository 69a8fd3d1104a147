"""Peak share of the page pool that was referenced in the window
(``pages_in_use`` over the allocatable pages; page 0 is scratch)."""


def read(run):
    used = [s[1] for s in run.get("samples", ())]
    if not used:
        return None
    return 100.0 * max(used) / (run["num_pages"] - 1)
