"""scheduler: the 95th percentile of the time from when a request was due
to its first streamed token, over the requests due in the window, a failed
one counted as the window.  Open loop only: it is the queue's tail, which
at 0.8 of the knee swings too widely from run to run to hold a bound."""

from perfbench import stats


def read(layer):
    if layer.get("kind") != "serve" or layer["mix"].get("loop") != "open":
        return None
    ttft = layer["numbers"]["ttft_ms"]
    if not ttft:
        return None
    return stats.percentile(ttft, 95)
