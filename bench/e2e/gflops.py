"""The paper's rate, in GFLOP/s: 2 operations per intermediate product of
every call completed in the window, over the window's seconds (its start
to the completion of the last call that arrived inside it)."""


def read(win):
    if not win.calls or win.window_s <= 0.0:
        return None
    return 2.0 * win.products_per_call * len(win.calls) / win.window_s / 1e9
