"""Share of the cached rows a window layer did NOT read against what a
full layer read at the same steps, in percent, over the window's decode
steps: 100 x (1 - (window rows / window layers) / (full rows / full
layers)), the rows from the decode step's own counters
(``attn_window_rows``, ``attn_full_rows``: an active slot at position
``p`` reads ``min(p + 1, W)`` ring rows in each window layer and ``p +
1`` pool rows in each full layer).  0 where every slot is inside the
window — or where a window layer reads the whole history."""


def read(run):
    attn = run.get("attn")
    if not attn or not attn["attn_full_rows"]:
        return None
    periods = run["arch"]["num_hidden_layers"] // 4
    window = attn["attn_window_rows"] / (3 * periods)
    full = attn["attn_full_rows"] / periods
    return 100.0 * (1.0 - window / full)
