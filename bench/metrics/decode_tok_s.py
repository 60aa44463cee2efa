"""Output tokens delivered inside the window over the window's length."""
import stats


def read(w):
    return stats.rate(w.tokens_in_window, w.t0, w.t1)
